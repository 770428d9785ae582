"""The two readings a limit of ``harness/seq_check`` lies between, by
hand on the chip.

    python3 benchmarks/tools/seq_precision.py --workload <cell> --seeds 1,2,3

For each seed: the seeded model, three users' answers from the served
path (``SeqRecAlgorithm.batch_predict``), held to the float32 reference
by ``seq_check.check_one`` (the first reading: what the change gives);
then the same check of the answers the reference itself gives with both
operands of every product rounded to 8 bits (float8 e4m3), the nearest
precision below the configuration's bfloat16, which must come out as
not correct; and the served path once more with retention's state
accumulated in bfloat16. Prints one JSON line per seed."""

import argparse
import functools
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _answers(algo, model, users, num):
    from predictionio_tpu.templates import sessionrec

    got = dict(algo.batch_predict(model, [
        (i, sessionrec.Query(user=f"u{u}", num=num))
        for i, u in enumerate(users)]))
    return [[(int(s.item[1:]) + 1, s.score) for s in got[i].item_scores]
            for i in range(len(users))]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmarks.harness import device, seq_check, seq_data
    from benchmarks.harness.manifest import load_cell
    from benchmarks.reference import brumby_jnp
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import retention
    from predictionio_tpu.templates import sessionrec

    cell = load_cell(args.workload, args.manifest)
    device.claim(cell)
    num = int(cell.traffic["num"])
    algo = sessionrec.SeqRecAlgorithm(seq_data.algorithm_params(cell.config))
    kept = seqrec.power_retention
    for seed in [int(s) for s in args.seeds.split(",")]:
        model, histories, pool = seq_data.build_model(
            cell.config, cell.traffic, seed)
        users = [int(u) for u in dict.fromkeys(pool.tolist()) if u >= 0][:3]
        served = _answers(algo, model, users, num)
        seqrec.power_retention = functools.partial(
            kept, _state_dtype=jnp.bfloat16)
        jax.clear_caches()
        bf16_state = _answers(algo, model, users, num)
        seqrec.power_retention = kept
        jax.clear_caches()
        line = {"seed": seed, "users": users, "served": [], "fp8_operands": [],
                "bf16_state": [], "score_tol": seq_check.SCORE_TOL,
                "rank_tol": seq_check.RANK_TOL}
        refs, lows = [], []
        for target, dtype in ((refs, None), (lows, jnp.float8_e4m3fn)):
            brumby_jnp.set_operands(dtype)
            for u in users:
                target.append(np.asarray(brumby_jnp.last_logits(
                    model.device_tree, histories[u], cell.config),
                    np.float32))
        for n, u in enumerate(users):
            ref, low = refs[n], lows[n]
            allowed = low.copy()
            allowed[0] = -np.inf
            allowed[histories[u]] = -np.inf
            top = np.argsort(-allowed, kind="stable")[:num]
            for key, answer in (
                    ("served", served[n]), ("bf16_state", bf16_state[n]),
                    ("fp8_operands", [(int(i), float(low[i])) for i in top])):
                why, worst = seq_check.check_one(ref, histories[u], answer,
                                                 num)
                line[key].append({"why": why, **worst})
        brumby_jnp.set_operands(None)
        del model
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
