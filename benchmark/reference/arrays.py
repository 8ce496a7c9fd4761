"""The reference's arithmetic: plain ``jax.numpy`` NCHW layers, no
kernels, nothing of the program. ``ArrayOps(params, precision)`` computes
a layer table at one of the precisions of ``PRECISIONS``:

* ``None``: float32 throughout (``highest`` matmul precision is the
  caller's). What the losses, gradients and updates are followed in.
* ``"bfloat16"``: the precision the configurations state. Every tensor a
  layer reads or writes (activations, weights, gamma, beta, each layer's
  output) is rounded to bfloat16; arithmetic inside a layer, statistics
  and accumulation stay float32. It does not follow the program's
  roundings (two bfloat16 pipelines differ from each other about as much
  as each does from float32); it measures, on the same weights and rows,
  how much rounding noise storing tensors at that precision costs: the
  floor that ``check.excess_noise`` reads the program's noise against.
* the controls, one step BELOW the stated precision, each the bfloat16
  pipeline with one thing lowered: ``"int8_matmul"`` and ``"fp8_matmul"``
  round the two inputs of every convolution and matmul to 8 bits (one
  dynamic scale per tensor; the v5e's int8 rate is twice its bfloat16
  rate, so this is the step that tempts); ``"bf16_accumulate"`` keeps
  the running sums in bfloat16: a convolution's accumulator is rounded
  after every ``ACC_DEPTH`` multiply-adds or so (a kernel that keeps its
  accumulator tile in bfloat16 between blocks of the contraction), and
  BatchNorm's sums after each axis. They are never a timed path and
  forward-only.
"""
import jax.numpy as jnp
from jax import lax

ACC_DEPTH = 256    # multiply-adds between two roundings of a bfloat16
#                    accumulator (in at most 16 groups of input channels)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _int8(x):
    """Symmetric per-tensor int8 round trip."""
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _fp8(x):
    """Per-tensor scaled float8 e4m3 round trip (3 bits of mantissa where
    bfloat16 has 7)."""
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _same(x):
    return x


# name -> (what a stored tensor is rounded to, what a matmul's inputs are
# rounded to, whether running sums are kept in bfloat16)
PRECISIONS = {
    None: (_same, _same, False),
    "bfloat16": (_bf16, _same, False),
    "int8_matmul": (_bf16, _int8, False),
    "fp8_matmul": (_bf16, _fp8, False),
    "bf16_accumulate": (_bf16, _same, True),
}


def _sum_bf16(x, axes):
    """A sum whose accumulator is bfloat16 between axes: summed over one
    axis at a time, rounded after each."""
    for axis in sorted(axes, reverse=True):
        x = _bf16(jnp.sum(x, axis=axis, keepdims=True))
    return x


class ArrayOps:
    """Computes a layer table of ``reference/<net>.py`` on arrays.
    ``params`` maps the program's parameter names to float32 arrays.
    BatchNorm uses the batch's statistics (training mode, biased
    variance, two passes)."""

    def __init__(self, params, precision=None, remat=True):
        self.params = params
        self.precision = precision
        self.store, self.mm_in, self.acc_bf16 = PRECISIONS[precision]
        self.remat = remat

    def block(self, fn, x):
        if not self.remat:
            return fn(self, x)
        import jax

        # one block's activations live at a time in the backward pass:
        # float32 at the cells' batch would not fit the chip otherwise
        run = lambda p, x: fn(ArrayOps(p, self.precision, remat=False), x)  # noqa: E731
        return jax.checkpoint(run)(self.params, x)

    def _conv(self, x, w, stride, pad, groups=1):
        return lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad)] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups)

    def conv(self, x, name, cout, k, stride, pad):
        x = self.mm_in(self.store(x))
        w = self.mm_in(self.store(self.params[name + "_weight"]))
        cin = x.shape[1]
        g = max(d for d in (16, 8, 4, 2, 1)
                if cin % d == 0 and (d == 1 or d * ACC_DEPTH <= cin * k * k)) \
            if self.acc_bf16 else 1
        if g == 1:
            return self.store(self._conv(x, w, stride, pad))
        # one grouped convolution gives each group of input channels'
        # partial sum for every output channel; they are then added up
        # in bfloat16, one at a time
        wg = w.reshape(cout, g, cin // g, k, k).transpose(1, 0, 2, 3, 4)
        part = self._conv(x, wg.reshape(g * cout, cin // g, k, k), stride,
                          pad, groups=g)
        part = _bf16(part.reshape(part.shape[0], g, cout, *part.shape[2:]))
        total = part[:, 0]
        for i in range(1, g):
            total = _bf16(total + part[:, i])
        return total

    def bn(self, x, name, eps, relu):
        gamma = self.store(self.params[name + "_gamma"]).reshape(1, -1, 1, 1)
        beta = self.store(self.params[name + "_beta"]).reshape(1, -1, 1, 1)
        if self.acc_bf16:
            n = x.shape[0] * x.shape[2] * x.shape[3]
            mean = _sum_bf16(x, (0, 2, 3)) / n
            var = _sum_bf16(jnp.square(x - mean), (0, 2, 3)) / n
        else:
            mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
            var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3),
                           keepdims=True)
        y = (x - mean) * lax.rsqrt(var + eps) * gamma + beta
        return self.store(jnp.maximum(y, 0.0) if relu else y)

    def add_relu(self, a, b):
        return self.store(jnp.maximum(a + b, 0.0))

    def pool(self, x, kind, k, stride, pad):
        window, strides = (1, 1, k, k), (1, 1, stride, stride)
        padding = ((0, 0), (0, 0), (pad, pad), (pad, pad))
        if kind == "max":
            return lax.reduce_window(x, -jnp.inf, lax.max, window, strides,
                                     padding)
        # the average counts the padding (the reference framework's rule)
        return self.store(lax.reduce_window(x, 0.0, lax.add, window, strides,
                                            padding) / float(k * k))

    def concat(self, xs):
        return jnp.concatenate(xs, axis=1)

    def global_avg(self, x):
        return self.store(jnp.mean(x, axis=(2, 3)))

    def fc(self, x, name, n):
        w = self.mm_in(self.store(self.params[name + "_weight"]))
        bias = self.store(self.params[name + "_bias"])
        return self.store(self.mm_in(self.store(x)) @ w.T + bias)
