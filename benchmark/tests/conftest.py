"""The benchmark's own tests: run by hand (``python -m pytest benchmark/tests``)
and in the chip rehearsal; not part of the repo's tier-1 suite."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# a size that a test run can hold
SMALL = {"rows": 30000, "holdout_rows": 5000, "params": {"num_leaves": 15}}

# a cell kept for later (PERF.md, Open questions): not in BENCHMARK.json, but
# its traffic mix, its faults and its comparison are tested all the same
VALID_CELL = {"name": "higgs.valid-sort", "config": "higgs",
              "traffic": "valid-sort", "chips": 1}
