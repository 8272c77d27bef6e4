"""benchmark/tests — run by hand, not part of the repo's tier-1 suite:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q            # fast ones
    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -m slow    # rehearsals

The slow ones drive `run.py --rehearse` end to end on the CPU: each compiles
(first time, minutes) or loads (afterwards, ~3 min) a pairing launch class.
"""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: a CPU rehearsal of a whole run")
