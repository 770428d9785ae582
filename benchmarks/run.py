"""One run of one cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The process that runs this claims the chip and hosts the system under
test; ``BENCHMARK.json`` names the cell's configuration and traffic
files, and the traffic file's ``kind`` names the module under
``benchmarks/kinds/`` that runs it. The last line of standard output is
the result; everything the program prints goes to standard error.
"""

import time

T_START = time.monotonic()      # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="another manifest of the same shape, relative to "
                         "the checkout (benchmarks/refused_cells.json)")
    args = ap.parse_args(argv)

    from benchmarks.harness.manifest import load_cell

    cell = load_cell(args.workload, args.manifest)
    kind = importlib.import_module(f"benchmarks.kinds.{cell.traffic['kind']}")
    with contextlib.redirect_stdout(sys.stderr):
        line = kind.run(cell, args, T_START)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
