"""The benchmark harness's own light tests, run with every PR.

`benchmark/metrics/*.py` read span, scope and counter names that
`mxnet_tpu/` writes: a PR that renames one turns a per-layer metric
`null` on the chip, and nothing under `tests/` would say so. The cases
live in `benchmark/tests/` (`pytest benchmark/tests` runs all of them);
this file collects the five light modules there under the driver's
`pytest tests/`, each case under its own name: the trace reduction on
recorded traces, the shape walk behind the rooflines, the readers of
the program's spans, the readers of the compiled step's account, and the
readers of the build record. The heavy rehearsals are collected by `test_benchmark_rehearsals.py` and
`test_benchmark_fit_lm.py`.
"""
import os
import sys

from dist_util import REPO

# what benchmark/tests/conftest.py does for a run from there
for _path in (REPO, os.path.join(REPO, "benchmark", "tests")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from test_trace_reduce import *    # noqa: E402,F401,F403
from test_walk import *            # noqa: E402,F401,F403
from test_program_spans import *   # noqa: E402,F401,F403
from test_step_account import *    # noqa: E402,F401,F403
from test_build_readers import *   # noqa: E402,F401,F403
