"""The two readings a limit of a reference module lies between, by
hand on the chip, for a cell of the ``serve_seq_ref_open`` kind.

    python3 benchmarks/tools/seq_ref_precision.py --workload <cell> --seeds 1,2 [--users 8] [--lower 3]

For each seed: the seeded model and ``--users`` users' answers from the
served path (``SeqRecAlgorithm.batch_predict``), each held to the
float32 reference under the resolutions of its near ties, best first
(the first reading: what the change gives, how many resolutions were
tried, the margin the one that agreed gave up, and what the reference's
own choice alone would have said). Then, for the first ``--lower`` users, the answer
the reference itself gives in each lower precision it knows
(``set_lower``: 8-bit operands, a bfloat16 softmax accumulator, a router
fed bfloat16 scores), held to the float32 reference the same way: each
has to come out as not correct. Prints one JSON line per seed."""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--users", type=int, default=8)
    ap.add_argument("--lower", type=int, default=3)
    args = ap.parse_args()

    from benchmarks.harness import device, seq_ref_check, seq_ref_data
    from benchmarks.harness.manifest import load_cell
    from predictionio_tpu.templates import sessionrec

    cell = load_cell(args.workload, args.manifest)
    device.claim(cell)
    num = int(cell.traffic["num"])
    reference = seq_ref_data.reference(cell.config)
    algo = sessionrec.SeqRecAlgorithm(
        seq_ref_data.algorithm_params(cell.config))
    for seed in [int(s) for s in args.seeds.split(",")]:
        model, histories, pool = seq_ref_data.build_model(
            cell.config, cell.traffic, seed)
        users = [int(u) for u in dict.fromkeys(pool.tolist())
                 if u >= 0][:args.users]
        line = {"seed": seed, "users": users, "served": [],
                "score_tol": reference.SCORE_TOL,
                "rank_tol": reference.RANK_TOL,
                "near_tie": reference.NEAR_TIE}
        def resolutions(u, steps):
            return reference.resolutions(
                model.device_tree, histories[u], cell.config,
                near_tie=reference.NEAR_TIE, max_steps=steps)

        for u in users:
            got = algo.batch_predict(
                model, [(0, sessionrec.Query(user=f"u{u}", num=num))])[0][1]
            answer = [(int(s.item[1:]) + 1, s.score) for s in got.item_scores]
            own = []                    # what the first resolution alone says

            def watched(found):
                for logits, cost in found:
                    if not own:
                        own.append(seq_ref_check.hold_to_resolutions(
                            [(logits, cost)], histories[u], answer, num,
                            reference))
                    yield logits, cost

            why, worst, tried, cost = seq_ref_check.hold_to_resolutions(
                watched(resolutions(u, reference.MAX_STEPS)),
                histories[u], answer, num, reference)
            line["served"].append({
                "why": why, **worst, "tried": tried, "margin_given_up": cost,
                "own_choice_why": own[0][0],
                "own_choice_score_diff": own[0][1]["score_diff"]})
        for what in ("operands", "softmax", "router"):
            line[what] = []
            for u in users[:args.lower]:
                reference.set_lower(what)
                low = np.asarray(reference.last_logits(
                    model.device_tree, histories[u], cell.config), np.float32)
                reference.set_lower(None)
                allowed = low.copy()
                allowed[0] = -np.inf
                allowed[histories[u]] = -np.inf
                top = np.argsort(-allowed, kind="stable")[:num]
                why, worst, tried, cost = seq_ref_check.hold_to_resolutions(
                    resolutions(u, 100), histories[u],
                    [(int(i), float(low[i])) for i in top], num, reference)
                line[what].append({"why": why, **worst, "tried": tried,
                                   "margin_given_up": cost})
        reference.set_lower(None)
        del model
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
