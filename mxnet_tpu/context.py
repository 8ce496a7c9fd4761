"""Device context.

TPU-native re-design of the reference's ``Context`` (``include/mxnet/base.h``
+ ``python/mxnet/context.py``): a ``Context`` names a logical device
(``cpu``/``tpu``) and resolves lazily to a concrete ``jax.Device``.

``mx.gpu(i)`` is kept as an alias for the accelerator (= TPU here) so the
reference's example scripts run unchanged.
"""
from __future__ import annotations

import threading
from typing import List, Optional

from .base import MXNetError

__all__ = ["Context", "cpu", "tpu", "gpu", "current_context", "num_devices"]


_ACCEL_TYPES = ("tpu", "gpu", "cuda")


class Context:
    """A logical device. ``device_type`` in {'cpu', 'tpu', 'gpu'};
    'gpu' is an alias for the accelerator backend (TPU)."""

    _default_ctx = threading.local()
    devtype2str = {1: "cpu", 2: "tpu", 3: "cpu_pinned"}
    devstr2type = {"cpu": 1, "tpu": 2, "gpu": 2, "cuda": 2, "cpu_pinned": 3}

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in Context.devstr2type:
            raise MXNetError("unknown device type %s" % device_type)
        # canonicalize gpu->tpu: single accelerator namespace
        self.device_typeid = Context.devstr2type[device_type]
        self.device_id = device_id

    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    # -- jax resolution ----------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device. Only THIS process's
        (addressable) devices are eligible — under jax.distributed,
        ``jax.devices()`` is global and would hand other hosts' devices
        out (reference analogue: a worker only drives its own GPUs)."""
        import jax

        if self.device_type in ("cpu", "cpu_pinned"):
            devs = jax.local_devices(backend="cpu")
        else:
            devs = _accelerator_devices()
            if not devs:
                raise MXNetError(
                    "%s: this process has no accelerator (jax found only "
                    "platform %r); use mx.cpu() to run on the host"
                    % (self, jax.local_devices()[0].platform))
        if self.device_id >= len(devs):
            raise MXNetError(
                "%s: device_id out of range (%d devices visible)" % (self, len(devs)))
        return devs[self.device_id]

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *args):
        Context._default_ctx.stack.pop()


def _accelerator_devices() -> List:
    import jax

    return [d for d in jax.local_devices() if d.platform != "cpu"]


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias for the accelerator device so reference scripts run unchanged."""
    return Context("tpu", device_id)


def current_context() -> Context:
    stack = getattr(Context._default_ctx, "stack", None)
    if stack:
        return stack[-1]
    return Context("cpu", 0)


def num_devices(device_type: str = "tpu") -> int:
    import jax

    if device_type == "cpu":
        return len(jax.devices("cpu"))
    return len(_accelerator_devices())
