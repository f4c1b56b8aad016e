"""The benchmark's own tests, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

(the repository's tier-1 run collects ``tests/`` only, not these)."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
