"""The benchmark's own tests run on the CPU: ``pytest benchmark/tests``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for path in (os.path.dirname(BENCH), HERE):
    if path not in sys.path:
        sys.path.insert(0, path)
