"""Runtime kernel compilation.

TPU-native re-design of the reference's MXRtc (``include/mxnet/mxrtc.h``,
``src/common/mxrtc.cc``, ``python/mxnet/rtc.py``): where the reference
compiled CUDA source strings with NVRTC and pushed them on NDArrays, here
user-supplied **Pallas kernel source** is compiled at runtime and invoked
on NDArrays. The kernel body gets ``pl``/``pltpu``/``jnp``/``jax`` in scope
and refs for each input and output, mirroring ``mx.rtc.Rtc(name, inputs,
outputs, kernel_source)``.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from .base import MXNetError
from .ndarray import NDArray
from .ops.pallas_kernels import pallas_call

__all__ = ["Rtc"]


class Rtc:
    """Compile + run an inline Pallas kernel.

    Parameters mirror the reference: ``name``; ``inputs``/``outputs`` as
    (name, NDArray) pairs declaring shapes/dtypes; ``kernel`` is the Python
    source of the kernel *body*. Inside the body, each input/output is a
    pallas Ref named ``<name>_ref``.

    Example::

        rtc = mx.rtc.Rtc("axpy",
                         [("x", x), ("y", y)], [("out", out)],
                         "out_ref[:] = 2.0 * x_ref[:] + y_ref[:]")
        rtc.push([x, y], [out])
    """

    def __init__(self, name: str, inputs: Sequence[Tuple[str, NDArray]],
                 outputs: Sequence[Tuple[str, NDArray]], kernel: str):
        import jax

        self.name = name
        self._in_names = [n for n, _ in inputs]
        self._out_names = [n for n, _ in outputs]
        self._out_shapes = [(tuple(a.shape), np.dtype(a.dtype))
                            for _, a in outputs]
        arg_names = ["%s_ref" % n for n in self._in_names + self._out_names]
        src_lines = ["def __kernel__(%s):" % ", ".join(arg_names)]
        body = kernel.strip("\n")
        for line in (body.splitlines() or ["pass"]):
            src_lines.append("    " + line)
        src = "\n".join(src_lines)

        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        try:
            from jax.experimental.pallas import tpu as pltpu
        except Exception:  # pragma: no cover
            pltpu = None
        scope: Dict = {"jnp": jnp, "jax": jax, "pl": pl, "pltpu": pltpu,
                       "np": np}
        try:
            exec(compile(src, "<rtc:%s>" % name, "exec"), scope)
        except SyntaxError as e:
            raise MXNetError("Rtc '%s': kernel failed to compile: %s"
                             % (name, e))
        self._kernel = scope["__kernel__"]

        def call(*in_arrays):
            # interpreter when lowered for the CPU, Mosaic on a TPU:
            # decided by where the pushed NDArrays live, per call
            return pallas_call(
                self._kernel, *in_arrays,
                out_shape=tuple(jax.ShapeDtypeStruct(s, d)
                                for s, d in self._out_shapes))

        self._call = jax.jit(call)

    def push(self, inputs: List[NDArray], outputs: List[NDArray],
             grid_dims=None, block_dims=None):
        """Run the kernel (reference Rtc.push; grid/block dims are accepted
        for API parity but Pallas/XLA choose the schedule)."""
        if len(inputs) != len(self._in_names) or \
                len(outputs) != len(self._out_names):
            raise MXNetError("Rtc '%s': input/output arity mismatch" % self.name)
        results = self._call(*[a.handle for a in inputs])
        if not isinstance(results, (tuple, list)):
            results = (results,)
        for out, res in zip(outputs, results):
            def _assign(out=out, res=res):
                out._data = res
            from .engine import get_engine

            get_engine().push(_assign, mutable_vars=[out._var])
