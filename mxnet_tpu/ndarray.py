"""Imperative NDArray on top of jax.Array.

TPU-native re-design of the reference's NDArray
(``include/mxnet/ndarray.h:33-388``, ``src/ndarray/ndarray.cc``): an
asynchronous device array whose every mutation routes through the dependency
engine. Here the device buffer is an immutable ``jax.Array`` and "mutation"
rebinds the buffer; XLA's async dispatch gives the same compute/IO overlap
the reference engine provided, and :meth:`wait_to_read` maps to
``block_until_ready`` (reference ``WaitToRead`` → ``Engine::WaitForVar``).

The reference registers NDArray functions into a C registry
(``ndarray.h:516-695``) that the Python frontend enumerates at import
(``python/mxnet/ndarray.py:1127-1306``); here the registry is
:data:`mxnet_tpu.base.Registry` and functions are registered directly.
"""
from __future__ import annotations

import struct
import warnings
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .base import MXNetError, Registry, DTYPE_NP_TO_ID, DTYPE_ID_TO_NP, mx_real_t
from .context import Context, cpu, current_context
from .engine import get_engine
from . import telemetry as _tel

__all__ = ["NDArray", "array", "empty", "zeros", "ones", "full", "arange",
           "concatenate", "load", "save", "onehot_encode", "waitall"]


def _jnp():
    import jax.numpy as jnp
    return jnp


def _require_dtype(dtype):
    """Validate an explicitly requested dtype against jax's x64 mode.

    With x64 disabled (the TPU default), jax silently narrows int64/
    float64/uint64 to their 32-bit forms — the kind of divergence that
    bites custom-op authors. The reference honors 64-bit dtypes
    (``include/mxnet/base.h`` mshadow dtype tables), so here a 64-bit
    request is either honored (x64 enabled) or rejected loudly — never
    truncated.
    """
    if dtype is None:
        # np.dtype(None) is float64 — an unset dtype means the reference
        # default (mx_real_t), not a 64-bit request
        return np.dtype(mx_real_t)
    dt = np.dtype(dtype)
    if dt.itemsize == 8 and dt.kind in "iuf":
        from jax import config as _jax_config

        if not _jax_config.read("jax_enable_x64"):
            narrowed = np.dtype(dt.str[:-1] + "4")
            raise MXNetError(
                "dtype %s requested but jax is running with x64 disabled, "
                "which would silently narrow it to %s. Request %s "
                "explicitly, or enable 64-bit mode (JAX_ENABLE_X64=1 / "
                "jax.config.update('jax_enable_x64', True)) to honor it."
                % (dt, narrowed, narrowed))
    return dt


def _shares_buffer(a, b) -> Optional[bool]:
    """Tri-state aliasing check for two jax arrays.

    ``jax.device_put`` (and no-op ``astype``) on a same-device array may
    return a NEW ``jax.Array`` handle to the SAME underlying buffer, so an
    identity check is insufficient: donating one handle deletes the data
    both see.

    Returns ``True``/``False`` when aliasing can be VERIFIED via buffer
    pointers — single-buffer arrays through ``unsafe_buffer_pointer``,
    sharded arrays by intersecting per-shard pointers from
    ``addressable_shards``. Returns ``None`` when no pointer is
    obtainable (backend without the API, committed-elsewhere shards):
    callers guarding donation must treat ``None`` as possibly-aliased
    and copy defensively (``is not False``), not assume distinct."""
    if a is b:
        return True
    try:
        return a.unsafe_buffer_pointer() == b.unsafe_buffer_pointer()
    except Exception:
        pass
    try:
        def ptrs(x):
            return {s.data.unsafe_buffer_pointer()
                    for s in x.addressable_shards}

        pa, pb = ptrs(a), ptrs(b)
        if not pa or not pb:
            return None
        return bool(pa & pb)
    except Exception:
        return None


class NDArray:
    """An n-dimensional device array with imperative, engine-ordered ops."""

    __slots__ = ("_data", "_ctx", "_var", "writable")

    def __init__(self, data, ctx: Optional[Context] = None, writable: bool = True):
        import jax

        self._ctx = ctx if ctx is not None else current_context()
        if not isinstance(data, jax.Array):
            host = np.asarray(data)
            data = jax.device_put(host, self._ctx.jax_device())
            # attribute feed-loop vs kvstore H2D traffic in snapshots
            _tel.inc("ndarray.h2d_bytes", host.nbytes)
            _tel.inc("ndarray.h2d_transfers")
        self._data = data
        self._var = get_engine().new_variable()
        self.writable = writable

    # -- basic properties --------------------------------------------------
    def _sync_data(self):
        """Under an async host engine, lazily-produced arrays may not have a
        buffer yet; wait on the engine var before touching ``_data``."""
        d = self._data
        if d is None:
            get_engine().wait_for_var(self._var)
            d = self._data
        return d

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._sync_data().shape)

    @property
    def dtype(self):
        return np.dtype(self._sync_data().dtype)

    @property
    def size(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def context(self) -> Context:
        return self._ctx

    @property
    def handle(self):
        """The raw jax.Array (the reference exposed the C handle)."""
        return self._sync_data()

    # -- synchronization (reference ndarray.h:221-238) ---------------------
    def wait_to_read(self):
        self._sync_data().block_until_ready()

    def wait_to_write(self):
        self.wait_to_read()

    # -- host transfer -----------------------------------------------------
    def asnumpy(self) -> np.ndarray:
        self.wait_to_read()
        return np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("asscalar requires size-1 array, got %s" % (self.shape,))
        return self.asnumpy().reshape(())[()]

    def astype(self, dtype) -> "NDArray":
        dt = _require_dtype(dtype)
        return _new_from(self, lambda x: x.astype(dt), [self])

    # -- placement ---------------------------------------------------------
    def copyto(self, other: Union["NDArray", Context]) -> "NDArray":
        """Copy to another array (shapes must match) or to a context
        (reference ``CopyFromTo``, ``src/ndarray/ndarray.cc:226-291``)."""
        import jax

        if isinstance(other, Context):
            return _new_from(self,
                             lambda x: jax.device_put(x, other.jax_device()),
                             [self], ctx=other)
        if not isinstance(other, NDArray):
            raise MXNetError("copyto expects NDArray or Context")
        if other.shape != self.shape:
            raise MXNetError("copyto shape mismatch %s vs %s" % (self.shape, other.shape))

        def _do():
            new = jax.device_put(
                self._data.astype(other.dtype), other._ctx.jax_device())
            if _shares_buffer(new, self._data) is not False:
                # device_put is a no-copy on same-device transfers; copyto
                # must yield a DISTINCT buffer, or donating either array
                # (optimizer / executor-aux donation) would delete the
                # other's data. None (unverifiable) copies too: a spare
                # copy is cheap, a deleted live buffer is not
                import jax.numpy as jnp

                new = jnp.copy(new)
            other._data = new
        get_engine().push(_do, const_vars=[self._var], mutable_vars=[other._var])
        return other

    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    def copy(self) -> "NDArray":
        return _new_from(self, lambda x: x + 0, [self])

    # -- shape manipulation ------------------------------------------------
    def reshape(self, shape) -> "NDArray":
        if isinstance(shape, int):
            shape = (shape,)
        return _new_from(self, lambda x: x.reshape(_expand_reshape(self.shape, shape)), [self])

    @property
    def T(self) -> "NDArray":
        return _new_from(self, lambda x: x.T, [self])

    def slice(self, start: int, stop: int) -> "NDArray":
        return self[start:stop]

    def __getitem__(self, key) -> "NDArray":
        return _new_from(self, lambda x: x[key], [self])

    def __setitem__(self, key, value):
        if not self.writable:
            raise MXNetError("NDArray is not writable")
        jnp = _jnp()
        if isinstance(value, NDArray):
            if value is self and key == slice(None):
                return
            val = value._data
            reads = [value._var] if value is not self else []
        else:
            val = value
            reads = []
        full_write = key == slice(None)

        def _do():
            if full_write and not np.isscalar(val):
                if isinstance(val, np.ndarray):
                    # own the storage: jnp.asarray zero-copy borrows
                    # host memory on CPU, so the array would alias the
                    # caller's buffer — a later caller mutation writes
                    # through us, and if the source is a view of a
                    # device buffer (asnumpy), the borrow pins that
                    # buffer against donation (the fused step then
                    # silently holds two copies of the state)
                    # ... and where this array lives, not on JAX's default
                    # device: a host copy of the parameters written from
                    # ``asnumpy()`` (``Module.get_params``) would move
                    # onto the accelerator, a second copy of the model
                    # (left to JAX where that IS the default device: the
                    # array stays uncommitted, free to follow a mesh)
                    import jax

                    devs = self._data.devices()
                    dev = next(iter(devs)) if len(devs) == 1 else None
                    v = jnp.array(val, dtype=self.dtype, device=None
                                  if dev == jax.devices()[0] else dev)
                else:
                    v = jnp.asarray(val, dtype=self.dtype)
                if v.shape != self.shape:
                    v = jnp.broadcast_to(v, self.shape)
                self._data = v
            else:
                self._data = self._data.at[key].set(
                    jnp.asarray(val, dtype=self.dtype))
        get_engine().push(_do, const_vars=reads, mutable_vars=[self._var])

    # -- arithmetic --------------------------------------------------------
    def __add__(self, other):
        return _binary(self, other, lambda a, b: a + b)

    __radd__ = __add__

    def __sub__(self, other):
        return _binary(self, other, lambda a, b: a - b)

    def __rsub__(self, other):
        return _binary(self, other, lambda a, b: b - a)

    def __mul__(self, other):
        return _binary(self, other, lambda a, b: a * b)

    __rmul__ = __mul__

    def __truediv__(self, other):
        return _binary(self, other, lambda a, b: a / b)

    def __rtruediv__(self, other):
        return _binary(self, other, lambda a, b: b / a)

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __pow__(self, other):
        return _binary(self, other, lambda a, b: a ** b)

    def __neg__(self):
        return _new_from(self, lambda x: -x, [self])

    def __iadd__(self, other):
        return _inplace(self, other, lambda a, b: a + b)

    def __isub__(self, other):
        return _inplace(self, other, lambda a, b: a - b)

    def __imul__(self, other):
        return _inplace(self, other, lambda a, b: a * b)

    def __idiv__(self, other):
        return _inplace(self, other, lambda a, b: a / b)

    __itruediv__ = __idiv__

    # comparisons return 0/1 arrays like the reference's broadcast ops
    def __eq__(self, other):  # type: ignore[override]
        return _binary(self, other, lambda a, b: (a == b).astype(a.dtype))

    def __ne__(self, other):  # type: ignore[override]
        return _binary(self, other, lambda a, b: (a != b).astype(a.dtype))

    def __gt__(self, other):
        return _binary(self, other, lambda a, b: (a > b).astype(a.dtype))

    def __ge__(self, other):
        return _binary(self, other, lambda a, b: (a >= b).astype(a.dtype))

    def __lt__(self, other):
        return _binary(self, other, lambda a, b: (a < b).astype(a.dtype))

    def __le__(self, other):
        return _binary(self, other, lambda a, b: (a <= b).astype(a.dtype))

    def __hash__(self):
        return id(self)

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of 0-d array")
        return self.shape[0]

    def __repr__(self):
        return "<NDArray %s @%s>" % ("x".join(map(str, self.shape)), self._ctx)

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")


def _expand_reshape(cur_shape, shape):
    """Support -1 and 0 (copy-dim) entries like the reference Reshape."""
    shape = list(shape)
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = cur_shape[i]
    return tuple(shape)


def _new_from_multi(ctx, fn, reads: Sequence[NDArray],
                    n_out: int) -> List[NDArray]:
    """Engine-ordered op: read ``reads``' vars, write ``n_out`` fresh
    output NDArrays. ``fn(*datas)`` returns a list of n_out jax arrays."""
    eng = get_engine()
    outs = []
    for _ in range(n_out):
        o = NDArray.__new__(NDArray)
        o._ctx = ctx
        o._var = eng.new_variable()
        o.writable = True
        o._data = None  # type: ignore[assignment]
        outs.append(o)

    def _do():
        results = fn(*[r._data for r in reads])
        for o, r in zip(outs, results):
            o._data = r
        return [o._data for o in outs]
    eng.push(_do, const_vars=[r._var for r in reads],
             mutable_vars=[o._var for o in outs])
    return outs


def _new_from(src: NDArray, fn, reads: Sequence[NDArray], ctx=None, dtype=None) -> NDArray:
    return _new_from_multi(ctx or src._ctx,
                           lambda *datas: [fn(*datas)], reads, 1)[0]


def _binary(lhs: NDArray, rhs, fn) -> NDArray:
    if isinstance(rhs, NDArray):
        return _new_from(lhs, fn, [lhs, rhs])
    return _new_from(lhs, lambda a: fn(a, rhs), [lhs])


def _inplace(lhs: NDArray, rhs, fn) -> NDArray:
    if not lhs.writable:
        raise MXNetError("in-place op on non-writable NDArray")
    if isinstance(rhs, NDArray):
        reads = [rhs._var]

        def _do():
            lhs._data = fn(lhs._data, rhs._data)
    else:
        reads = []

        def _do():
            lhs._data = fn(lhs._data, rhs)
    get_engine().push(_do, const_vars=reads, mutable_vars=[lhs._var])
    return lhs


# ---------------------------------------------------------------------------
# creation functions
# ---------------------------------------------------------------------------

def array(source, ctx: Optional[Context] = None, dtype=None) -> NDArray:
    if isinstance(source, NDArray):
        source = source.asnumpy()
    if dtype is not None:
        dtype = _require_dtype(dtype)
    arr = np.asarray(source, dtype=dtype)
    if dtype is None and arr.dtype in (np.float64, np.int64, np.uint64):
        # reference default: float32 arrays (mx_real_t). uint64 included:
        # letting it reach jax would silently truncate to uint32
        arr = arr.astype(mx_real_t)
    return NDArray(arr, ctx=ctx)


def empty(shape, ctx=None, dtype=mx_real_t) -> NDArray:
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=mx_real_t) -> NDArray:
    dtype = _require_dtype(dtype)
    jnp = _jnp()
    if isinstance(shape, int):
        shape = (shape,)
    ctx = ctx if ctx is not None else current_context()
    return NDArray(jnp.zeros(shape, dtype=np.dtype(dtype),
                             device=ctx.jax_device()), ctx=ctx)


def ones(shape, ctx=None, dtype=mx_real_t) -> NDArray:
    dtype = _require_dtype(dtype)
    jnp = _jnp()
    if isinstance(shape, int):
        shape = (shape,)
    ctx = ctx if ctx is not None else current_context()
    return NDArray(jnp.ones(shape, dtype=np.dtype(dtype),
                            device=ctx.jax_device()), ctx=ctx)


def full(shape, val, ctx=None, dtype=mx_real_t) -> NDArray:
    dtype = _require_dtype(dtype)
    jnp = _jnp()
    if isinstance(shape, int):
        shape = (shape,)
    ctx = ctx if ctx is not None else current_context()
    return NDArray(jnp.full(shape, val, dtype=np.dtype(dtype),
                            device=ctx.jax_device()), ctx=ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=mx_real_t) -> NDArray:
    dtype = _require_dtype(dtype)
    arr = np.arange(start, stop, step, dtype=np.dtype(dtype))
    if repeat != 1:
        arr = np.repeat(arr, repeat)
    return NDArray(arr, ctx=ctx)


def waitall():
    get_engine().wait_for_all()


# ---------------------------------------------------------------------------
# registered NDArray functions (reference registry ndarray.h:516-695)
# ---------------------------------------------------------------------------

_ndarray_fn_registry: Registry = Registry.get_registry("ndarray_function")


def _register_fn(name):
    def _wrap(fn):
        _ndarray_fn_registry.register(name)(fn)
        globals()[name] = fn
        if name not in __all__:
            __all__.append(name)
        return fn
    return _wrap


def _unary_fn(name, jfn):
    @_register_fn(name)
    def _fn(data: NDArray, out: Optional[NDArray] = None) -> NDArray:
        res = _new_from(data, jfn, [data])
        if out is not None:
            return res.copyto(out)
        return res
    _fn.__name__ = name
    return _fn


jnp_lazy = _jnp  # alias used in lambdas below

_unary_fn("exp", lambda x: jnp_lazy().exp(x))
_unary_fn("log", lambda x: jnp_lazy().log(x))
_unary_fn("sqrt", lambda x: jnp_lazy().sqrt(x))
_unary_fn("square", lambda x: x * x)
_unary_fn("abs", lambda x: jnp_lazy().abs(x))
_unary_fn("sign", lambda x: jnp_lazy().sign(x))
_unary_fn("round", lambda x: jnp_lazy().round(x))
_unary_fn("ceil", lambda x: jnp_lazy().ceil(x))
_unary_fn("floor", lambda x: jnp_lazy().floor(x))
_unary_fn("cos", lambda x: jnp_lazy().cos(x))
_unary_fn("sin", lambda x: jnp_lazy().sin(x))
_unary_fn("relu", lambda x: jnp_lazy().maximum(x, 0))
_unary_fn("sigmoid", lambda x: 1.0 / (1.0 + jnp_lazy().exp(-x)))
_unary_fn("tanh", lambda x: jnp_lazy().tanh(x))


@_register_fn("dot")
def dot(lhs: NDArray, rhs: NDArray) -> NDArray:
    return _new_from(lhs, lambda a, b: _jnp().dot(a, b), [lhs, rhs])


@_register_fn("maximum")
def maximum(lhs, rhs) -> NDArray:
    if not isinstance(lhs, NDArray):
        lhs, rhs = rhs, lhs
    return _binary(lhs, rhs, lambda a, b: _jnp().maximum(a, b))


@_register_fn("minimum")
def minimum(lhs, rhs) -> NDArray:
    if not isinstance(lhs, NDArray):
        lhs, rhs = rhs, lhs
    return _binary(lhs, rhs, lambda a, b: _jnp().minimum(a, b))


@_register_fn("clip")
def clip(data: NDArray, a_min, a_max) -> NDArray:
    return _new_from(data, lambda x: _jnp().clip(x, a_min, a_max), [data])


def _reduce_fn(name, jname):
    @_register_fn(name)
    def _fn(data: NDArray, axis=None, keepdims=False) -> NDArray:
        def _do(x):
            r = getattr(_jnp(), jname)(x, axis=axis, keepdims=keepdims)
            if r.ndim == 0:
                r = r.reshape((1,))
            return r
        return _new_from(data, _do, [data])
    _fn.__name__ = name
    return _fn


sum = _reduce_fn("sum", "sum")  # noqa: A001
max = _reduce_fn("max", "max")  # noqa: A001
min = _reduce_fn("min", "min")  # noqa: A001
mean = _reduce_fn("mean", "mean")


@_register_fn("argmax_channel")
def argmax_channel(data: NDArray) -> NDArray:
    return _new_from(data, lambda x: _jnp().argmax(x, axis=1).astype(x.dtype), [data])


@_register_fn("norm")
def norm(data: NDArray) -> NDArray:
    return _new_from(
        data, lambda x: _jnp().sqrt(_jnp().sum(x.astype("float32") ** 2)).reshape((1,)),
        [data])


@_register_fn("transpose")
def transpose(data: NDArray, axes=None) -> NDArray:
    return _new_from(data, lambda x: _jnp().transpose(x, axes), [data])


@_register_fn("broadcast_to")
def broadcast_to(data: NDArray, shape) -> NDArray:
    return _new_from(data, lambda x: _jnp().broadcast_to(x, tuple(shape)), [data])


def concatenate(arrays: Sequence[NDArray], axis: int = 0) -> NDArray:
    if not arrays:
        raise MXNetError("concatenate needs at least one array")
    return _new_from(arrays[0],
                     lambda *xs: _jnp().concatenate(xs, axis=axis), list(arrays))


@_register_fn("onehot_encode")
def onehot_encode(indices: NDArray, out: NDArray) -> NDArray:
    """Reference ``onehot_encode`` NDArray function (``ndarray.cc:723+``)."""
    depth = out.shape[1]

    def _do():
        jnp = _jnp()
        idx = indices._data.astype("int32")
        out._data = (idx[:, None] == jnp.arange(depth)[None, :]).astype(out.dtype)
    get_engine().push(_do, const_vars=[indices._var], mutable_vars=[out._var])
    return out


@_register_fn("choose_element_0index")
def choose_element_0index(lhs: NDArray, rhs: NDArray) -> NDArray:
    """out[i] = lhs[i, rhs[i]] (reference matrix_op)."""
    return _new_from(
        lhs, lambda a, b: a[_jnp().arange(a.shape[0]), b.astype("int32")], [lhs, rhs])


@_register_fn("element_mask")
def element_mask(lhs: NDArray, rhs: NDArray) -> NDArray:
    """out[i, ...] = lhs[i, ...] * rhs[i] — per-row mask broadcast
    (reference SimpleOp element_mask, broadcast_mask_op-inl.h:23-60)."""
    if lhs.ndim < 2 or rhs.ndim != 1 or lhs.shape[0] != rhs.shape[0]:
        raise MXNetError(
            "element_mask: source tensor should be 2D or more, mask 1D "
            "with matching first dim; got lhs=%s rhs=%s"
            % (lhs.shape, rhs.shape))

    def _do(a, b):
        mask = b.reshape((a.shape[0],) + (1,) * (a.ndim - 1))
        return a * mask.astype(a.dtype)
    return _new_from(lhs, _do, [lhs, rhs])


def _check_crop_region(shape, begin, end, what="crop_assign"):
    """Validate a [begin, end) region against shape; returns the region
    shape. Shared by the imperative fns here and the symbolic
    CropAssign/CropAssignScalar ops (ops/tensor.py)."""
    if len(begin) != len(shape) or len(end) != len(shape):
        raise MXNetError("%s: begin/end must cover all %d axes"
                         % (what, len(shape)))
    for b, e, d in zip(begin, end, shape):
        if not (0 <= b <= e <= d):
            raise MXNetError("%s: invalid range [%d, %d) on axis of size "
                             "%d" % (what, b, e, d))
    return tuple(e - b for b, e in zip(begin, end))


@_register_fn("crop_assign")
def crop_assign(lhs: NDArray, rhs: NDArray, begin, end) -> NDArray:
    """Write rhs into lhs[begin:end) (reference SimpleOp _crop_assign,
    matrix_op-inl.h:452-524; functional here — returns a new array)."""
    region = _check_crop_region(lhs.shape, begin, end)
    if rhs.shape != region:
        raise MXNetError("crop_assign: rhs shape %s does not match region "
                         "%s" % (rhs.shape, region))
    idx = tuple(slice(b, e) for b, e in zip(begin, end))
    return _new_from(lhs, lambda a, b: a.at[idx].set(b.astype(a.dtype)),
                     [lhs, rhs])


@_register_fn("crop_assign_scalar")
def crop_assign_scalar(data: NDArray, scalar, begin, end) -> NDArray:
    """Fill data[begin:end) with a scalar (reference SimpleOp
    _crop_assign_scalar, matrix_op-inl.h:526-600)."""
    _check_crop_region(data.shape, begin, end)
    idx = tuple(slice(b, e) for b, e in zip(begin, end))
    return _new_from(
        data, lambda a: a.at[idx].set(np.asarray(scalar, dtype=a.dtype)),
        [data])


# ---------------------------------------------------------------------------
# serialization (reference ndarray.h:304-315 save/load with names)
# ---------------------------------------------------------------------------

_MAGIC = 0x54505541525241  # "TPUARRA"


def save_to_stream(f, data) -> None:
    """Write the container to an open binary file object (used by both
    :func:`save` and the C ABI's raw-bytes functions)."""
    if isinstance(data, dict):
        names = list(data.keys())
        arrays = [data[k] for k in names]
    elif isinstance(data, (list, tuple)):
        names = []
        arrays = list(data)
    elif isinstance(data, NDArray):
        names, arrays = [], [data]
    else:
        raise MXNetError("save expects NDArray, list or dict of NDArray")
    f.write(struct.pack("<QQQ", _MAGIC, 0, len(arrays)))
    for arr in arrays:
        np_arr = arr.asnumpy()
        dtype_id = DTYPE_NP_TO_ID[np.dtype(np_arr.dtype)]
        f.write(struct.pack("<I", np_arr.ndim))
        f.write(struct.pack("<%dq" % np_arr.ndim, *np_arr.shape))
        f.write(struct.pack("<I", dtype_id))
        raw = np_arr.tobytes()
        f.write(struct.pack("<Q", len(raw)))
        f.write(raw)
    f.write(struct.pack("<Q", len(names)))
    for name in names:
        b = name.encode("utf-8")
        f.write(struct.pack("<Q", len(b)))
        f.write(b)


def _read_exact(f, n: int, what: str) -> bytes:
    """Read exactly ``n`` bytes or raise a named MXNetError. Central
    torn-file detection: a checkpoint truncated mid-write (preemption,
    full disk) surfaces as "truncated ... file X", never as a raw
    struct.error half-way through a resume."""
    raw = f.read(n)
    if len(raw) != n:
        raise MXNetError("invalid NDArray file %s: truncated (wanted %d "
                         "bytes, got %d — partial/torn write?)"
                         % (what, n, len(raw)))
    return raw


def load_from_stream(f, what: str = "<stream>"):
    """Read a container from an open binary file object; returns list or
    dict like :func:`load`. Short reads anywhere in the container raise
    :class:`MXNetError` naming ``what``."""
    header = f.read(24)
    if len(header) < 24:
        raise MXNetError("invalid NDArray file %s: truncated header" % what)
    magic, _, n = struct.unpack("<QQQ", header)
    if magic != _MAGIC:
        raise MXNetError("invalid NDArray file %s" % what)
    arrays = []
    for _ in range(n):
        ndim, = struct.unpack("<I", _read_exact(f, 4, what))
        shape = struct.unpack("<%dq" % ndim,
                              _read_exact(f, 8 * ndim, what)) if ndim else ()
        dtype_id, = struct.unpack("<I", _read_exact(f, 4, what))
        nbytes, = struct.unpack("<Q", _read_exact(f, 8, what))
        raw = _read_exact(f, nbytes, what)
        if dtype_id not in DTYPE_ID_TO_NP:
            raise MXNetError("invalid NDArray file %s: unknown dtype id %d"
                             % (what, dtype_id))
        arr = np.frombuffer(raw, dtype=DTYPE_ID_TO_NP[dtype_id]).reshape(shape)
        dt = arr.dtype
        if dt.itemsize == 8 and dt.kind in "iuf":
            from jax import config as _jax_config

            if not _jax_config.read("jax_enable_x64"):
                # loading must not hard-fail on 64-bit checkpoints (saved
                # under x64 or by the reference): narrow deliberately,
                # loudly — unlike creation, where the request is rejected
                narrowed = np.dtype(dt.str[:-1] + "4")
                warnings.warn(
                    "%s: narrowing stored %s array to %s (jax x64 "
                    "disabled; set JAX_ENABLE_X64=1 to load losslessly)"
                    % (what, dt, narrowed), stacklevel=2)
                arr = arr.astype(narrowed)
        arrays.append(array(arr, dtype=arr.dtype))
    n_names, = struct.unpack("<Q", _read_exact(f, 8, what))
    names = []
    for _ in range(n_names):
        ln, = struct.unpack("<Q", _read_exact(f, 8, what))
        names.append(_read_exact(f, ln, what).decode("utf-8"))
    if names:
        if len(names) != len(arrays):
            raise MXNetError("corrupt NDArray file: name/array count mismatch")
        return dict(zip(names, arrays))
    return arrays


def save(fname: str, data) -> None:
    """Save a list or str-keyed dict of NDArrays to a binary container.
    ``fname`` may be a URI (``mem://``, registered schemes) — reference
    dmlc::Stream S3/HDFS dispatch (see :mod:`mxnet_tpu.filesystem`)."""
    from .filesystem import open_uri

    with open_uri(fname, "wb") as f:
        save_to_stream(f, data)


def load(fname: str):
    """Load NDArrays saved by :func:`save`. Returns list or dict."""
    from .filesystem import open_uri

    with open_uri(fname, "rb") as f:
        return load_from_stream(f, fname)
