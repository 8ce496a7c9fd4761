"""mxnet_tpu — a TPU-native deep learning framework with the capabilities of
2016-era MXNet (reference: hschen0712/mxnet).

The public API mirrors ``import mxnet as mx``:

* ``mx.nd`` — imperative NDArray over jax.Array + dependency engine
* ``mx.sym`` — symbolic graph with autodiff, compiled whole-graph to XLA
* ``mx.io`` — data iterators (NDArray/MNIST/CSV/ImageRecord) with prefetch
* ``mx.kv`` — KVStore (local / device / tpu_sync collective all-reduce)
* ``mx.mod`` / ``mx.model`` — Module and FeedForward training loops
* ``mx.optimizer`` / ``mx.metric`` / ``mx.init`` — training utilities
"""
from __future__ import annotations

import os as _os


def _place_compile_cache():
    """XLA's persistent compile cache, placed before anything compiles.
    ``JAX_COMPILATION_CACHE_DIR`` in the environment wins and JAX reads
    it itself — nothing is set here. Otherwise the cache lives at a FIXED
    path beside the package (the path is part of how a run finds the
    cache again, so never a temp name, a pid or a time); JAX's own
    thresholds then write every program that took a second or more to
    compile, which is every train step and serve bucket.

    The cache is for the chip. A process pinned to the CPU
    (``JAX_PLATFORMS=cpu``) gets none: this jaxlib's CPU loader logs a
    machine-mismatch error on every cache hit, and CPU executables that
    travel with the checkout to another host may not run there.
    Setting config options initialises no backend."""
    if "JAX_COMPILATION_CACHE_DIR" in _os.environ \
            or _os.environ.get("JAX_PLATFORMS") == "cpu":
        return   # before importing jax: a CPU worker stays a light import
    import jax

    if jax.config.jax_platforms == "cpu":   # pinned in code, not by env
        return
    root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    jax.config.update("jax_compilation_cache_dir",
                      _os.path.join(root, ".jax_cache"))


_place_compile_cache()

from .base import MXNetError  # noqa: E402
from .context import Context, cpu, gpu, tpu, current_context, num_devices
from . import engine
from . import ndarray
from . import ndarray as nd
from . import random
from .ndarray import NDArray
from .name import NameManager
from .attribute import AttrScope

__version__ = "0.1.0"

# Submodules below are imported lazily-but-eagerly in dependency order; each
# maps to a reference frontend module (python/mxnet/*.py).
from . import operator        # noqa: E402  (registers the Custom op before
#                                            symbol generates creators)
from . import symbol          # noqa: E402
from .ndarray_ops import init_ndarray_ops  # noqa: E402

init_ndarray_ops(ndarray)  # SimpleOp unification: ops usable imperatively
from . import symbol as sym   # noqa: E402
from .symbol import Symbol    # noqa: E402
from . import executor        # noqa: E402
from . import initializer     # noqa: E402
from . import initializer as init  # noqa: E402
from . import optimizer       # noqa: E402
from . import metric          # noqa: E402
from . import lr_scheduler    # noqa: E402
from . import io              # noqa: E402
from . import io_pipeline     # noqa: E402
from . import io_cache        # noqa: E402
from . import recordio        # noqa: E402
from . import filesystem      # noqa: E402
from . import kvstore         # noqa: E402
from . import kvstore as kv   # noqa: E402
from . import callback        # noqa: E402
from . import monitor         # noqa: E402
from .monitor import Monitor  # noqa: E402
from . import model           # noqa: E402
from .model import FeedForward  # noqa: E402
from . import module          # noqa: E402
from . import module as mod   # noqa: E402
from . import visualization   # noqa: E402
from . import visualization as viz  # noqa: E402
from . import test_utils      # noqa: E402
from . import export          # noqa: E402
from . import profiler        # noqa: E402
from . import telemetry       # noqa: E402
from . import tracing         # noqa: E402
