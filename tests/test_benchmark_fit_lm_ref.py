"""The second language-model driver's rehearsals, run with every PR.

`benchmark/tests/test_fit_lm_ref.py` under the driver's `pytest tests/`:
the `fit_lm_ref` driver end to end at toy width against the `olmo_hybrid`
reference, the runs `correct` must refuse, the controls, and the new
per-layer readers. Each case shows under its own name.
"""
import os
import sys

from dist_util import REPO

for _path in (REPO, os.path.join(REPO, "benchmark", "tests")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from test_fit_lm_ref import *   # noqa: E402,F401,F403
