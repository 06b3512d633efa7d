"""The benchmark's own tests run on the CPU: nothing here is a device number."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH, os.path.join(BENCH, "readers")):
    if p not in sys.path:
        sys.path.insert(0, p)
