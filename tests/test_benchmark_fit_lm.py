"""The language-model driver's rehearsals, run with every PR.

`benchmark/tests/test_fit_lm.py` under the driver's `pytest tests/`: the
`fit_lm` driver end to end at toy width against its reference, and the
runs `correct` must refuse (a capacity that drops rows, half the
learning rate, a switched-off update, a selection bias that never
moves). Each case shows under its own name.
"""
import os
import sys

from dist_util import REPO

for _path in (REPO, os.path.join(REPO, "benchmark", "tests")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from test_fit_lm import *   # noqa: E402,F401,F403
