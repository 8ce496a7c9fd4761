"""The fifth language model's rehearsals, run with every PR.

`benchmark/tests/test_fit_lm_bailing.py` under the driver's `pytest tests/`:
the `fit_lm_ref` driver end to end at toy width against the `bailing_hybrid`
reference (per-channel delta-rule mixers, latent attention with values
narrower than keys under a head-wise gate, group-limited routed experts
beside a shared one), the runs `correct` must refuse, the seven controls,
`part_of` over every node, and one shared traced run. Each case shows under
its own name. A file of its own, so that `--dist loadfile` gives it a worker
beside the one that takes `test_bailing_hybrid.py`.
"""
import os
import sys

from dist_util import REPO

for _path in (REPO, os.path.join(REPO, "benchmark", "tests")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from test_fit_lm_bailing import *   # noqa: E402,F401,F403
