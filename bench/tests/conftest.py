"""Tests of the benchmark itself, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), os.path.join(BENCH, "traffic"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
