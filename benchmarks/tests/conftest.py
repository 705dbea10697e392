"""Tests of the benchmark's own code.  Run by hand from the repo root:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

They sit outside ``tests/`` and are no part of the tier-1 run.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
