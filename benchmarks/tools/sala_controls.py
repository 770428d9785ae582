"""The controls that prove the check of a MiniCPM-SALA cell can see
each mechanism, by hand on the chip at the cell's size.

    python3 benchmarks/tools/sala_controls.py --workload <cell> --seed <n> [--users 2] [--qk-norm-init 1.0]

For each of ``--users`` users the float32 reference's logits of the last
position (``reference/minicpm_sala_jnp.last_logits``) as the
configuration states them, and with one mechanism taken away: stage 2
replaced by plain causal attention (``dense_len`` above the history),
the selection replaced by the forced blocks alone (``topk`` = the first
blocks + the local window's), and the lightning state dropped at every
256th position (what a chunk scan that lost its carry would compute).
Each has to move the logits, and the top ``num`` of them, by more than
``SCORE_TOL``. ``--qk-norm-init`` draws the model with other ``q_norm``
/ ``k_norm`` weights than the file's (the reading at 1.0 is why the
file says 2.0). Prints one JSON line per user."""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--users", type=int, default=2)
    ap.add_argument("--qk-norm-init", type=float, default=None)
    args = ap.parse_args()

    from benchmarks.harness import device, seq_ref_data
    from benchmarks.harness.manifest import load_cell
    from predictionio_tpu.templates import sessionrec

    cell = load_cell(args.workload, args.manifest)
    device.claim(cell)
    config = dict(cell.config)
    if args.qk_norm_init is not None:
        config["qk_norm_init"] = args.qk_norm_init
    reference = seq_ref_data.reference(config)
    sparse = config["sparse_config"]
    forced = sparse["init_blocks"] + sparse["window_size"] // sparse["block_size"]
    controls = {
        "dense": {"sparse_config": {**sparse,
                                    "dense_len": config["history_len"]}},
        "forced_only": {"sparse_config": {**sparse, "topk": forced}},
        "lightning_cut": {"control_lightning_cut": 256}}
    model, histories, pool = seq_ref_data.build_model(config, cell.traffic,
                                                      args.seed)
    num = int(cell.traffic["num"])
    weights = sessionrec._as_device_tree(model)
    users = [int(u) for u in dict.fromkeys(pool.tolist()) if u >= 0]
    for u in users[:args.users]:
        t0 = time.monotonic()
        own = np.asarray(reference.last_logits(
            weights, histories[u], config), np.float32)
        line = {"seed": args.seed, "user": u,
                "qk_norm_init": config["qk_norm_init"],
                "score_tol": reference.SCORE_TOL,
                "logits_std": float(own.std()),
                "reference_s": time.monotonic() - t0}
        allowed = own.copy()
        allowed[0] = -np.inf
        allowed[histories[u]] = -np.inf
        top = np.argsort(-allowed, kind="stable")[:num]
        line["top"] = [float(own[i]) for i in top]
        for name, over in controls.items():
            other = np.asarray(reference.last_logits(
                weights, histories[u], {**config, **over}),
                np.float32)
            line[name] = {"moved_most": float(np.max(np.abs(other - own))),
                          "moved_most_in_top": float(np.max(np.abs(
                              other[top] - own[top])))}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
