"""CPU tests of the benchmark's own code.  Run from the repo root:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
