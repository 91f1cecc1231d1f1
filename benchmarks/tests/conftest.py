"""The benchmark's own tests: pure-Python checks of the yardstick run
in-process; anything that touches JAX runs ``benchmarks/run.py`` or
``fault_runner.py`` as a child held to the CPU."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
