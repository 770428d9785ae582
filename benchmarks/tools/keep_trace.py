"""One run of one cell, as ``benchmarks/run.py`` makes it, that leaves
its profile behind: the kinds delete their work directory, so by hand

    python3 benchmarks/tools/keep_trace.py <DIR> --workload <cell> --seed <n> --seconds <s> --trace 1

copies the ``.xplane.pb`` to ``DIR/<cell>.<seed>.xplane.pb`` before the
reduction reads it, for ``tools/dump_xplane.py`` (``--runs`` lists a
program's runs beside the profile's edges). The result line is the
run's own.
"""

import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run  # noqa: E402
from benchmarks.harness import xplane  # noqa: E402


def main(out: str, argv: list) -> int:
    def words(flag):
        return argv[argv.index(flag) + 1]

    load_dir = xplane.load_dir

    def keeping(trace_dir: str):
        path = xplane.find_xplane(trace_dir)
        if path:
            os.makedirs(out, exist_ok=True)
            shutil.copy(path, os.path.join(
                out, f"{words('--workload')}.{words('--seed')}.xplane.pb"))
        return load_dir(trace_dir)

    xplane.load_dir = keeping
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2:]))
