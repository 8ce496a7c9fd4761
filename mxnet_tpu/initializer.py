"""Weight initializers (reference ``python/mxnet/initializer.py``)."""
from __future__ import annotations

import re
from typing import Dict, List, Optional

import numpy as np

from .base import MXNetError, Registry
from .ndarray import NDArray
from . import random as _random

__all__ = ["Initializer", "Uniform", "Normal", "Xavier", "MSRAPrelu",
           "Orthogonal", "Zero", "One", "Constant", "Load", "Mixed"]

_REG: Registry = Registry.get_registry("initializer")


class Initializer:
    """Base: dispatch by parameter name suffix, like the reference."""

    def __call__(self, name: str, arr: NDArray):
        if name.startswith("upsampling"):
            self._init_bilinear(name, arr)
        elif name.endswith("_A_log") or name.endswith("_dt_bias") \
                or (name.endswith("_D") and len(arr.shape) == 1):
            # ``SSMScan``'s per-head vectors, by its argument names;
            # before "bias": dt_bias is one
            self._init_ssm(name, arr)
        elif name.endswith("bias"):
            self._init_bias(name, arr)
        elif name.endswith("gamma"):
            self._init_gamma(name, arr)
        elif name.endswith("beta"):
            self._init_beta(name, arr)
        elif name.endswith("weight"):
            self._init_weight(name, arr)
        elif name.endswith("parameters"):
            # fused-RNN flat parameter blob (cuDNN-style)
            self._init_weight(name, arr)
        elif name.endswith("moving_mean") or name.endswith("moving_avg") \
                or name.endswith("expert_rows"):
            self._init_zero(name, arr)
        elif name.endswith("state") or name.endswith("state_cell") \
                or name.endswith("init_h") or name.endswith("init_c"):
            # RNN initial states default to zero
            self._init_zero(name, arr)
        elif name.endswith("moving_var"):
            self._init_one(name, arr)
        else:
            self._init_default(name, arr)

    def _init_zero(self, _, arr):
        arr[:] = 0.0

    def _init_one(self, _, arr):
        arr[:] = 1.0

    def _init_bias(self, _, arr):
        arr[:] = 0.0

    def _init_gamma(self, _, arr):
        arr[:] = 1.0

    def _init_beta(self, _, arr):
        arr[:] = 0.0

    def _init_ssm(self, name, arr):
        """A state-space scan's per-head parameters (``SSMScan``), spread
        over the heads as Mamba-2 draws them: ``A = -exp(A_log)`` from 1
        to 16, a step ``softplus(dt_bias)`` from 1e-3 to 0.1
        (log-spaced), skip ``D`` = 1."""
        n = max(arr.shape[0] - 1, 1)
        at = np.arange(arr.shape[0], dtype=np.float64) / n
        if name.endswith("_A_log"):
            arr[:] = np.log(1.0 + 15.0 * at).astype(np.float32)
        elif name.endswith("_dt_bias"):
            dt = np.exp(np.log(1e-3) + at * (np.log(0.1) - np.log(1e-3)))
            arr[:] = (dt + np.log(-np.expm1(-dt))).astype(np.float32)
        else:
            arr[:] = 1.0

    def _init_bilinear(self, _, arr):
        shape = arr.shape
        weight = np.zeros(np.prod(shape), dtype=np.float32)
        f = np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(np.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            weight[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        arr[:] = weight.reshape(shape)

    def _init_weight(self, name, arr):
        raise NotImplementedError

    def _init_default(self, name, arr):
        raise MXNetError(
            "unknown parameter name pattern '%s'; use a Mixed initializer" % name)


@_REG.register("uniform")
class Uniform(Initializer):
    def __init__(self, scale: float = 0.07):
        self.scale = scale

    def _init_weight(self, _, arr):
        _random.uniform(-self.scale, self.scale, out=arr)


@_REG.register("normal")
class Normal(Initializer):
    def __init__(self, sigma: float = 0.01):
        self.sigma = sigma

    def _init_weight(self, _, arr):
        _random.normal(0.0, self.sigma, out=arr)


@_REG.register("xavier")
class Xavier(Initializer):
    def __init__(self, rnd_type: str = "uniform", factor_type: str = "avg",
                 magnitude: float = 3.0):
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = magnitude

    def _init_weight(self, _, arr):
        shape = arr.shape
        fan_out = shape[0]
        fan_in = int(np.prod(shape[1:])) if len(shape) > 1 else shape[0]
        if self.factor_type == "avg":
            factor = (fan_in + fan_out) / 2.0
        elif self.factor_type == "in":
            factor = fan_in
        elif self.factor_type == "out":
            factor = fan_out
        else:
            raise MXNetError("invalid factor_type %s" % self.factor_type)
        scale = float(np.sqrt(self.magnitude / factor))
        if self.rnd_type == "uniform":
            _random.uniform(-scale, scale, out=arr)
        elif self.rnd_type == "gaussian":
            _random.normal(0.0, scale, out=arr)
        else:
            raise MXNetError("invalid rnd_type %s" % self.rnd_type)


@_REG.register("msraprelu")
class MSRAPrelu(Xavier):
    def __init__(self, factor_type: str = "avg", slope: float = 0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)


@_REG.register("orthogonal")
class Orthogonal(Initializer):
    def __init__(self, scale: float = 1.414, rand_type: str = "uniform"):
        self.scale = scale
        self.rand_type = rand_type

    def _init_weight(self, _, arr):
        nout = arr.shape[0]
        nin = int(np.prod(arr.shape[1:]))
        if self.rand_type == "uniform":
            tmp = np.random.uniform(-1.0, 1.0, (nout, nin))
        else:
            tmp = np.random.normal(0.0, 1.0, (nout, nin))
        u, _, v = np.linalg.svd(tmp, full_matrices=False)
        q = u if u.shape == (nout, nin) else v
        arr[:] = (self.scale * q).reshape(arr.shape)


@_REG.register("zero")
class Zero(Initializer):
    def _init_weight(self, _, arr):
        arr[:] = 0.0

    def _init_default(self, _, arr):
        arr[:] = 0.0


@_REG.register("one")
class One(Initializer):
    def _init_weight(self, _, arr):
        arr[:] = 1.0


class Constant(Initializer):
    def __init__(self, value: float):
        self.value = value

    def _init_weight(self, _, arr):
        arr[:] = self.value


class Load:
    """Initialize from a saved dict, falling back to ``default_init``
    (reference ``mx.init.Load``)."""

    def __init__(self, param, default_init: Optional[Initializer] = None,
                 verbose: bool = False):
        from . import ndarray as nd

        if isinstance(param, str):
            param = nd.load(param)
        self.param = {}
        for name, arr in param.items():
            self.param[name.replace("arg:", "").replace("aux:", "")] = arr
        self.default_init = default_init
        self.verbose = verbose

    def __call__(self, name: str, arr: NDArray):
        if name in self.param:
            if self.param[name].shape != arr.shape:
                raise MXNetError("Load: shape mismatch for '%s'" % name)
            arr[:] = self.param[name]
        else:
            if self.default_init is None:
                raise MXNetError("Load: no init for '%s'" % name)
            self.default_init(name, arr)


class Mixed:
    """Regex-pattern-dispatched initializers (reference ``mx.init.Mixed``)."""

    def __init__(self, patterns: List[str], initializers: List[Initializer]):
        if len(patterns) != len(initializers):
            raise MXNetError("Mixed: patterns and initializers must pair up")
        self.map = list(zip([re.compile(p) for p in patterns], initializers))

    def __call__(self, name: str, arr: NDArray):
        for prog, init in self.map:
            if prog.match(name):
                init(name, arr)
                return
        raise MXNetError("Mixed: no pattern matched '%s'; add '.*'" % name)
