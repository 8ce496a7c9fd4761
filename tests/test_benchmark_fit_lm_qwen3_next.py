"""The sixth language model's rehearsals, run with every PR.

`benchmark/tests/test_fit_lm_qwen3_next.py` under the driver's `pytest
tests/`: the `fit_lm_ref` driver end to end at toy width against the
`qwen3_next` reference (delta-rule mixers with two value heads a key head,
gated part-rotary attention, softmax-routed experts with the auxiliary loss
beside a gated shared expert), the runs `correct` must refuse, the eight
controls, `part_of` over every node, and one shared traced run. Each case
shows under its own name. A file of its own, so that `--dist loadfile` gives
it a worker beside the one that takes `test_qwen3_next.py`.
"""
import os
import sys

from dist_util import REPO

for _path in (REPO, os.path.join(REPO, "benchmark", "tests")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from test_fit_lm_qwen3_next import *   # noqa: E402,F401,F403
