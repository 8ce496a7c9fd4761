"""The fourth language model's rehearsals, run with every PR.

`benchmark/tests/test_fit_lm_lfm2.py` under the driver's `pytest tests/`: the
`fit_lm_ref` driver end to end at toy width against the `lfm2_moe` reference
(gated short-convolution mixers, normalised grouped heads, routed experts
with no shared one, a tied head), the runs `correct` must refuse, the
controls, `part_of` over every node, and the two new per-layer readers over
one shared traced run. Each case shows under its own name. A file of its own,
so that `--dist loadfile` gives it a worker beside the one that takes
`test_lfm2_moe.py`.
"""
import os
import sys

from dist_util import REPO

for _path in (REPO, os.path.join(REPO, "benchmark", "tests")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from test_fit_lm_lfm2 import *   # noqa: E402,F401,F403
