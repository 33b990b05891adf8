"""The benchmark's own tests, on the CPU: ``pytest bench/tests``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), os.path.join(BENCH, "traffic"), BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
