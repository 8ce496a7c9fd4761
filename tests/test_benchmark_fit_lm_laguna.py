"""The seventh language model's rehearsals, run with every PR.

`benchmark/tests/test_fit_lm_laguna.py` under the driver's `pytest tests/`:
the `fit_lm_ref` driver end to end at toy width against the `laguna`
reference (full and sliding-window attention, more query heads on the
windowed layers, a gate a head, scaled rotary, a dense layer and
sigmoid-routed experts beside a shared one), the runs `correct` must refuse,
the ten controls, `part_of` over every node, and one shared traced run. Each
case shows under its own name. A file of its own, so that `--dist loadfile`
gives it a worker beside the ones that take `test_laguna.py` and
`test_attention_window.py`.
"""
import os
import sys

from dist_util import REPO

for _path in (REPO, os.path.join(REPO, "benchmark", "tests")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from test_fit_lm_laguna import *   # noqa: E402,F401,F403
