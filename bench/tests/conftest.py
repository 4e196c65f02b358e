"""CPU tests of the benchmark harness at tiny sizes.  Nothing here loads
the TPU's library: JAX runs on the CPU, and the chip is never asked for."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
