"""Tests of the benchmark itself.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

The tier-1 command collects ``tests/`` only, so these neither raise nor
lower its count.  Nothing here describes a TPU topology.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
