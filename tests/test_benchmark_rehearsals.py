"""The benchmark harness's heavy rehearsals, run with every PR.

Of `benchmark/tests/`, beside `test_benchmark_harness.py`: the float32
references against the program and the image driver's rehearsals, which
show that `correct` refuses a halved learning rate, a halved momentum, a
switched-off update. Each case shows under its own name; the language
model's are in `test_benchmark_fit_lm.py` (one name is in both modules,
and two files spread over two workers).
"""
import os
import sys

from dist_util import REPO

for _path in (REPO, os.path.join(REPO, "benchmark", "tests")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from test_reference import *   # noqa: E402,F401,F403
from test_rehearsal import *   # noqa: E402,F401,F403
