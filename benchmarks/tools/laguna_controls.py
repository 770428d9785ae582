"""The readings the limits of ``reference/laguna_jnp`` lie between, and
the controls that prove the check of a Laguna cell can see each
mechanism and the nearest precision below the stated one: by hand on the
chip at the cell's size.

    python3 benchmarks/tools/laguna_controls.py --workload <cell> --seed <n> [--served 4] [--controls 1]
        [--users 2904,17] [--near-ties 0.03,0.1] [--lower operands]

**Served** (one JSON line a user, ``--served`` users, those of
``--users`` first): the answer of the served path
(``SeqRecAlgorithm.batch_predict``) held to the float32 reference as
``harness/seq_ref_check`` holds it: the reference follows the served
path's expert choices where its own scores call them near ties
(``resolutions``), under each margin of ``--near-ties`` (the reference
module's ``NEAR_TIE`` when not given). Beside the differences: the most
an adopted token gave up, how many tokens were adopted and refused, and
the expert layers whose last position the reference computed from
another choice than the served one (``last_refused``). ``followed``
holds it once more with every served choice adopted: its ``margin`` is
the most the served path's choices lie under the reference's own, the
reading ``NEAR_TIE`` has to lie above.

**Controls** (one line a user, ``--controls`` users): the float32
reference with one mechanism taken away -- the sliding layers without
their window (causal over every key), no output gate, full-width rotary
in the full layers -- and in each lower precision it knows (``LOWER``;
``operands`` is 8-bit operands). Each stands for a served path with
that fault, so its own top ``num`` and their scores are held to the
float32 reference exactly as a served answer is: the reference is
offered the faulty path's choices and follows them where they are near
ties, under each margin of ``--near-ties``. ``caught`` says whether
the check refuses it; each of the three mechanisms and ``operands`` has
to be. ``--lower`` names the lower precisions read (all when not given).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CONTROLS = {"no_window": {"control_no_window": True},
            "no_gate": {"control_no_gate": True},
            "full_rotary": {"control_full_rotary": True}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--served", type=int, default=4)
    ap.add_argument("--controls", type=int, default=1)
    ap.add_argument("--users", default="")
    ap.add_argument("--near-ties", default="")
    ap.add_argument("--lower", default="")
    args = ap.parse_args()

    from benchmarks.harness import device, seq_ref_check, seq_ref_data
    from benchmarks.harness.manifest import load_cell
    from predictionio_tpu.templates import sessionrec

    cell = load_cell(args.workload, args.manifest)
    device.claim(cell)
    config = cell.config
    reference = seq_ref_data.reference(config)
    model, histories, pool = seq_ref_data.build_model(config, cell.traffic,
                                                      args.seed)
    algo = sessionrec.SeqRecAlgorithm(seq_ref_data.algorithm_params(config))
    num = int(cell.traffic["num"])
    weights = sessionrec._as_device_tree(model)
    asked = [int(u) for u in args.users.split(",") if u]
    users = list(dict.fromkeys(
        asked + [int(u) for u in pool.tolist() if u >= 0]))
    ties = [float(t) for t in args.near_ties.split(",") if t] or \
        [reference.NEAR_TIE]
    limits = {"score_tol": reference.SCORE_TOL,
              "rank_tol": reference.RANK_TOL, "near_tie": reference.NEAR_TIE}

    def held(u, answer, choices, near_tie):
        """``answer`` [(item, score)] of a path that chose ``choices``,
        held to the float32 reference as the check holds it, the
        reference adopting those choices within ``near_tie``."""
        logits, chose = reference.answer(weights, histories[u], config,
                                         choices, near_tie)
        logits = np.asarray(logits, np.float32)
        why, worst, _, _ = seq_ref_check.hold_to_resolutions(
            [(logits, chose["margin"])], histories[u], answer, num,
            reference)
        line = {"caught": why is not None, **worst,
                "margin": chose["margin"], "adopted": chose["adopted"],
                "refused": chose["refused"]}
        if choices is not None and chose["ids"] is not None:
            last = len(histories[u]) - 1
            line["last_refused"] = [
                n for n in range(len(choices))
                if set(chose["ids"][n, last].tolist())
                != set(np.asarray(choices[n, last]).tolist())]
        return logits, line

    for u in users[:args.served]:
        t0 = time.monotonic()
        got = algo.batch_predict(
            model, [(0, sessionrec.Query(user=f"u{u}", num=num))])[0][1]
        answer = [(int(s.item[1:]) + 1, s.score) for s in got.item_scores]
        choices = reference.served_choices(weights, histories[u], config)
        line = {"served": u, "seed": args.seed, **limits}
        for near_tie in ties:
            logits, line[str(near_tie)] = held(u, answer, choices, near_tie)
        line["followed"] = held(u, answer, choices, np.inf)[1]
        top = np.sort(logits)[::-1][:num + 1]
        print(json.dumps({**line, "logits_std": float(logits.std()),
                          "top_gaps": [float(g) for g in -np.diff(top)],
                          "s": time.monotonic() - t0}), flush=True)

    def faulty(u, over=None):
        """The answer of the reference with a fault, as a served path
        with that fault would give it, held to the sound reference."""
        logits, chose = reference.answer(weights, histories[u],
                                         {**config, **(over or {})})
        reference.set_lower(None)
        logits = np.asarray(logits, np.float32)
        allowed = logits.copy()
        allowed[0] = -np.inf                    # no PAD, none of the history
        allowed[histories[u]] = -np.inf
        top = np.argsort(-allowed, kind="stable")[:num]
        answer = [(int(i), float(logits[i])) for i in top]
        found = {}
        for near_tie in ties:
            sound, line = held(u, answer, chose["ids"], near_tie)
            line.pop("last_refused", None)
            found[str(near_tie)] = {
                "moved_most": float(np.max(np.abs(logits - sound))), **line}
        return found

    for u in users[:args.controls]:
        t0 = time.monotonic()
        line = {"controls": u, "seed": args.seed, **limits}
        for name, over in CONTROLS.items():
            line[name] = faulty(u, over)
        for what in [w for w in args.lower.split(",") if w] or \
                reference.LOWER:
            reference.set_lower(what)
            line[what] = faulty(u)
        line["s"] = time.monotonic() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
