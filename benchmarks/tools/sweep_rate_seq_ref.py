"""``tools/sweep_rate_seq.py`` for a cell of the ``serve_seq_ref_open``
kind: the same sweep and the same rule for "sustained", with the model
and the deployed engine of ``harness/seq_ref_data`` (the configuration's
file says how it is read) in the place of ``harness/seq_data``'s.

    python3 benchmarks/tools/sweep_rate_seq_ref.py --workload <cell> --rates 1,2,3 [--seconds 40]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.harness import seq_data, seq_ref_data  # noqa: E402
from benchmarks.tools import sweep_rate_seq  # noqa: E402

if __name__ == "__main__":
    for name in ("build_model", "deployed_engine"):
        setattr(seq_data, name, getattr(seq_ref_data, name))
    sys.exit(sweep_rate_seq.main())
