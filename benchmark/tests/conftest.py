"""The benchmark's own tests run on the CPU, in seconds, beside the repo's
tier-1 tests (they are not part of them): `python -m pytest benchmark/tests -q`."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
