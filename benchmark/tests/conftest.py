"""Tests of the benchmark's own arithmetic; run by hand, outside tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
