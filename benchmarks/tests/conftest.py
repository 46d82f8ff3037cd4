"""The benchmark's own tests, run by hand (they are not tier-1):

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q

Pure-arithmetic tests run in seconds. The rehearsal and fault tests drive
whole cells at tiny sizes on the CPU and compile the verify kernels there
(minutes, cold; the checkout's .jax_cache serves later runs).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
