"""Neural-network layer operators.

TPU-native implementations of the reference's layer ops
(``src/operator/*-inl.h``). Convolution/pooling/batchnorm lower straight to
XLA (``lax.conv_general_dilated`` / ``reduce_window``), which tiles them
onto the MXU — the TPU equivalent of the reference's cuDNN fast path
(``src/operator/cudnn_*-inl.h``). Layout is NCHW like the reference; XLA
re-lays-out internally for the systolic array.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import numpy as np

from ..base import MXNetError
from .registry import Operator, OpContext, Param, REQUIRED, register_op


def _jax():
    import jax
    return jax


def _jnp():
    import jax.numpy as jnp
    return jnp


# ---------------------------------------------------------------------------
# FullyConnected (reference src/operator/fully_connected-inl.h)
# ---------------------------------------------------------------------------
@register_op("FullyConnected")
class FullyConnected(Operator):
    name_hint = "fullyconnected"
    PARAMS = {
        "num_hidden": Param(int, REQUIRED, "number of hidden units"),
        "no_bias": Param(bool, False, "whether to disable bias"),
    }

    def list_arguments(self):
        return ["data", "weight"] if self.no_bias else ["data", "weight", "bias"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("FullyConnected: data shape unknown")
        n = data[0]
        d = int(np.prod(data[1:])) if len(data) > 1 else 1
        shapes = [data, (self.num_hidden, d)]
        if not self.no_bias:
            shapes.append((self.num_hidden,))
        return shapes, [(n, self.num_hidden)], []

    def remat_results(self, in_shapes, in_types):
        """The product: ``2 x rows x K x N`` operations to run again
        against ``rows x N`` values to hold."""
        rows, k = in_shapes[0][0], int(np.prod(in_shapes[0][1:]))
        return [("output",
                 rows * self.num_hidden * np.dtype(in_types[0]).itemsize,
                 2 * rows * k * self.num_hidden)]

    def weight_grad_flops(self, in_shapes):
        """``x^T dy``: ``2 x rows x K x N``, the rows contracted."""
        rows, k = in_shapes[0][0], int(np.prod(in_shapes[0][1:]))
        return {1: 2 * rows * k * self.num_hidden}

    def apply(self, ctx, inputs, aux):
        jnp = _jnp()
        data = inputs[0]
        w = inputs[1]
        x = data.reshape((data.shape[0], -1))
        out = jnp.dot(x, w.T)
        if not self.no_bias:
            out = out + inputs[2]
        return [ctx.keep(out, "output")], []


# ---------------------------------------------------------------------------
# Activation (reference src/operator/activation-inl.h)
# ---------------------------------------------------------------------------
@register_op("Activation")
class Activation(Operator):
    name_hint = "activation"
    PARAMS = {"act_type": Param(str, REQUIRED,
                                "relu/sigmoid/tanh/softrelu/silu/relu2")}

    def apply(self, ctx, inputs, aux):
        jnp = _jnp()
        x = inputs[0]
        act = self.act_type
        if act == "relu":
            out = jnp.maximum(x, 0)
        elif act == "sigmoid":
            out = _jax().nn.sigmoid(x)
        elif act == "tanh":
            out = jnp.tanh(x)
        elif act == "softrelu":
            out = _jax().nn.softplus(x)
        elif act == "silu":
            out = _jax().nn.silu(x)
        elif act == "relu2":
            # squared ReLU (So et al., Primer, arXiv:2109.08668)
            out = jnp.square(jnp.maximum(x, 0))
        else:
            raise MXNetError("unknown act_type %s" % act)
        return [out], []


@register_op("LeakyReLU")
class LeakyReLU(Operator):
    """reference src/operator/leaky_relu-inl.h (leaky/prelu/elu/rrelu)."""

    name_hint = "leakyrelu"
    PARAMS = {
        "act_type": Param(str, "leaky"),
        "slope": Param(float, 0.25),
        "lower_bound": Param(float, 0.125),
        "upper_bound": Param(float, 0.334),
    }

    def list_arguments(self):
        return ["data", "gamma"] if self.act_type == "prelu" else ["data"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("LeakyReLU: data shape unknown")
        if self.act_type == "prelu":
            return [data, (data[1],)], [data], []
        return [data], [data], []

    def apply(self, ctx, inputs, aux):
        jnp = _jnp()
        x = inputs[0]
        act = self.act_type
        if act == "leaky":
            out = jnp.where(x > 0, x, self.slope * x)
        elif act == "elu":
            out = jnp.where(x > 0, x, self.slope * (jnp.exp(x) - 1.0))
        elif act == "prelu":
            gamma = inputs[1].reshape((1, -1) + (1,) * (x.ndim - 2))
            out = jnp.where(x > 0, x, gamma * x)
        elif act == "rrelu":
            if ctx.is_train and ctx.rng is not None:
                slope = _jax().random.uniform(
                    ctx.rng, x.shape, dtype=x.dtype,
                    minval=self.lower_bound, maxval=self.upper_bound)
            else:
                slope = (self.lower_bound + self.upper_bound) / 2.0
            out = jnp.where(x > 0, x, slope * x)
        else:
            raise MXNetError("unknown act_type %s" % act)
        return [out], []


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (reference convolution-inl.h:76-489)
# ---------------------------------------------------------------------------
def _conv_out_dim(x, k, s, p, d):
    dk = d * (k - 1) + 1
    return (x + 2 * p - dk) // s + 1


def _spatial_letters(nd: int) -> str:
    """Spatial chars for dimension_numbers; must avoid N/C/O/I."""
    if nd == 1:
        return "W"
    if nd == 2:
        return "HW"
    if nd == 3:
        return "DHW"
    raise MXNetError("unsupported spatial rank %d" % nd)


_VALID_LAYOUTS = {"NCW", "NWC", "NCHW", "NHWC", "NCDHW", "NDHWC"}


def _layout_is_nhwc(layout):
    """Validate + classify a layout string: channels-last -> True.
    None means the NCHW default; anything outside the supported set is
    an error (a typo'd layout must not silently run as NCHW)."""
    if layout is None:
        return False
    lay = str(layout).upper()
    if lay not in _VALID_LAYOUTS:
        raise MXNetError("unsupported layout '%s' (supported: %s)"
                         % (layout, sorted(_VALID_LAYOUTS)))
    return lay.endswith("C")


class _ConvBase(Operator):
    PARAMS = {
        "kernel": Param("shape", REQUIRED, "(kh, kw)"),
        "num_filter": Param(int, REQUIRED),
        "stride": Param("shape", None),
        "pad": Param("shape", None),
        "dilate": Param("shape", None),
        "num_group": Param(int, 1),
        "no_bias": Param(bool, False),
        "workspace": Param(int, 512, "ignored; XLA plans memory"),
        "cudnn_tune": Param(str, None, "ignored on TPU"),
        "layout": Param(str, None, "NCHW (default) or NHWC — TPU-first "
                        "extension: NHWC keeps channels on the minor "
                        "(lane) axis, the layout the TPU vector unit "
                        "wants, avoiding compiler-inserted transposes"),
    }

    def _is_nhwc(self):
        return _layout_is_nhwc(self.layout)

    def list_arguments(self):
        return ["data", "weight"] if self.no_bias else ["data", "weight", "bias"]

    def _norm_params(self):
        nd = len(self.kernel)
        stride = self.stride or (1,) * nd
        pad = self.pad or (0,) * nd
        dilate = self.dilate or (1,) * nd
        return self.kernel, stride, pad, dilate


@register_op("Convolution")
class Convolution(_ConvBase):
    name_hint = "convolution"

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("Convolution: data shape unknown")
        kernel, stride, pad, dilate = self._norm_params()
        if len(data) != len(kernel) + 2:
            raise MXNetError("Convolution: data must be N,C,spatial*%d" % len(kernel))
        nhwc = self._is_nhwc()
        n = data[0]
        c = data[-1] if nhwc else data[1]
        sp_in = data[1:-1] if nhwc else data[2:]
        wshape = (self.num_filter, c // self.num_group) + tuple(kernel)
        out_sp = tuple(_conv_out_dim(sp_in[i], kernel[i], stride[i],
                                     pad[i], dilate[i])
                       for i in range(len(kernel)))
        shapes = [data, wshape]
        if not self.no_bias:
            shapes.append((self.num_filter,))
        out = (n,) + out_sp + (self.num_filter,) if nhwc \
            else (n, self.num_filter) + out_sp
        return shapes, [out], []

    def apply(self, ctx, inputs, aux):
        lax = _jax().lax
        kernel, stride, pad, dilate = self._norm_params()
        nd = len(kernel)
        spatial = _spatial_letters(nd)
        nhwc = self._is_nhwc()
        # weight stays OIHW in BOTH layouts (checkpoint-canonical); XLA
        # re-lays it out at compile time, so NHWC costs no transposes at
        # runtime on TPU
        act = "N" + spatial + "C" if nhwc else "NC" + spatial
        dn = (act, "OI" + spatial, act)
        out = lax.conv_general_dilated(
            inputs[0], inputs[1],
            window_strides=stride,
            padding=[(p, p) for p in pad],
            rhs_dilation=dilate,
            dimension_numbers=dn,
            feature_group_count=self.num_group,
            preferred_element_type=inputs[0].dtype
            if inputs[0].dtype == np.float32 else None,
        )
        if not self.no_bias:
            bshape = (1,) + (1,) * nd + (-1,) if nhwc \
                else (1, -1) + (1,) * nd
            out = out + inputs[2].reshape(bshape)
        return [out], []


@register_op("Deconvolution")
class Deconvolution(_ConvBase):
    """Transposed convolution (reference deconvolution-inl.h); weight layout
    (C_in, num_filter/num_group, kh, kw) as in the reference."""

    name_hint = "deconvolution"

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("Deconvolution: data shape unknown")
        kernel, stride, pad, dilate = self._norm_params()
        nhwc = self._is_nhwc()
        n = data[0]
        c = data[-1] if nhwc else data[1]
        sp_in = data[1:-1] if nhwc else data[2:]
        wshape = (c, self.num_filter // self.num_group) + tuple(kernel)
        out_sp = tuple((sp_in[i] - 1) * stride[i] - 2 * pad[i] + kernel[i]
                       for i in range(len(kernel)))
        shapes = [data, wshape]
        if not self.no_bias:
            shapes.append((self.num_filter,))
        out = (n,) + out_sp + (self.num_filter,) if nhwc \
            else (n, self.num_filter) + out_sp
        return shapes, [out], []

    def apply(self, ctx, inputs, aux):
        # gradient-of-conv formulation: input dilation by stride, padding
        # (dk-1-p), spatially flipped kernel — output (i-1)*s - 2p + dk,
        # matching the reference's deconv shape rule
        lax = _jax().lax
        jnp = _jnp()
        kernel, stride, pad, dilate = self._norm_params()
        nd = len(kernel)
        spatial = _spatial_letters(nd)
        act = "N" + spatial + "C" if self._is_nhwc() else "NC" + spatial
        dn = (act, "IO" + spatial, act)
        w = inputs[1]
        w = w[(slice(None), slice(None)) + (slice(None, None, -1),) * nd]
        padding = []
        for i in range(nd):
            dk = dilate[i] * (kernel[i] - 1) + 1
            padding.append((dk - 1 - pad[i], dk - 1 - pad[i]))
        out = lax.conv_general_dilated(
            inputs[0], w,
            window_strides=(1,) * nd,
            padding=padding,
            lhs_dilation=stride,
            rhs_dilation=dilate,
            dimension_numbers=dn,
            feature_group_count=self.num_group,
        )
        if not self.no_bias:
            bshape = (1,) + (1,) * nd + (-1,) if self._is_nhwc() \
                else (1, -1) + (1,) * nd
            out = out + inputs[2].reshape(bshape)
        return [out], []


# ---------------------------------------------------------------------------
# Pooling (reference pooling-inl.h; mshadow pool/unpool)
# ---------------------------------------------------------------------------
@register_op("Pooling")
class Pooling(Operator):
    name_hint = "pooling"
    PARAMS = {
        "kernel": Param("shape", REQUIRED),
        "pool_type": Param(str, "max", "max/avg/sum"),
        "stride": Param("shape", None),
        "pad": Param("shape", None),
        "global_pool": Param(bool, False),
        "layout": Param(str, None, "NCHW (default) or NHWC"),
    }

    def _is_nhwc(self):
        return _layout_is_nhwc(self.layout)

    def _sp_base(self):
        return 1 if self._is_nhwc() else 2

    def _norm(self, data_shape):
        nd = len(self.kernel)
        base = self._sp_base()
        if self.global_pool:
            kernel = tuple(data_shape[base + i] for i in range(nd))
            return kernel, (1,) * nd, (0,) * nd
        return self.kernel, self.stride or (1,) * nd, self.pad or (0,) * nd

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("Pooling: data shape unknown")
        kernel, stride, pad = self._norm(data)
        base = self._sp_base()
        if self.global_pool:
            out_sp = (1,) * len(kernel)
        else:
            out_sp = tuple(
                (data[base + i] + 2 * pad[i] - kernel[i]) // stride[i] + 1
                for i in range(len(kernel)))
        if self._is_nhwc():
            out = (data[0],) + out_sp + (data[-1],)
        else:
            out = data[:2] + out_sp
        return [data], [out], []

    def apply(self, ctx, inputs, aux):
        lax = _jax().lax
        jnp = _jnp()
        x = inputs[0]
        kernel, stride, pad = self._norm(x.shape)
        nd = len(kernel)
        if self._is_nhwc():
            window = (1,) + tuple(kernel) + (1,)
            strides = (1,) + tuple(stride) + (1,)
            padding = ((0, 0),) + tuple((p, p) for p in pad) + ((0, 0),)
        else:
            window = (1, 1) + tuple(kernel)
            strides = (1, 1) + tuple(stride)
            padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
        is_float = jnp.issubdtype(x.dtype, jnp.floating)  # incl. bfloat16
        if self.pool_type == "max":
            init = -jnp.inf if is_float else np.iinfo(x.dtype).min
            out = lax.reduce_window(x, init, lax.max, window, strides, padding)
        elif self.pool_type in ("avg", "sum"):
            out = lax.reduce_window(x, 0.0 if is_float else 0,
                                    lax.add, window, strides, padding)
            if self.pool_type == "avg":
                out = out / float(np.prod(kernel))
        else:
            raise MXNetError("unknown pool_type %s" % self.pool_type)
        return [out], []


# ---------------------------------------------------------------------------
# BatchNorm (reference batch_norm-inl.h; aux moving_mean/moving_var)
# ---------------------------------------------------------------------------
# CuDNNBatchNorm (reference cudnn_batch_norm.cc) is the same op with a
# vendor fast path; XLA is the single backend here, so it aliases.
@register_op("BatchNorm", aliases=("CuDNNBatchNorm",))
class BatchNorm(Operator):
    name_hint = "batchnorm"
    PARAMS = {
        "eps": Param(float, 1e-3),
        "momentum": Param(float, 0.9),
        "fix_gamma": Param(bool, True),
        "use_global_stats": Param(bool, False),
        "axis": Param(int, 1, "channel axis (1 = NCHW; -1 for NHWC)"),
    }

    def list_arguments(self):
        return ["data", "gamma", "beta"]

    def list_auxiliary_states(self):
        return ["moving_mean", "moving_var"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("BatchNorm: data shape unknown")
        c = (data[self.axis],)
        return [data, c, c], [data], [c, c]

    def apply(self, ctx, inputs, aux):
        jnp = _jnp()
        jax = _jax()
        x, gamma, beta = inputs
        moving_mean, moving_var = aux
        caxis = self.axis % x.ndim
        axes = tuple(i for i in range(x.ndim) if i != caxis)
        bshape = tuple(-1 if i == caxis else 1 for i in range(x.ndim))
        if self.fix_gamma:
            gamma = jnp.ones_like(gamma)
        use_batch_stats = ctx.is_train and not self.use_global_stats
        if use_batch_stats:
            # statistics in f32 even under bf16 mixed precision: a batch
            # mean over 1e5+ elements accumulated in bf16 loses the
            # moving averages (standard TPU mixed-precision practice).
            # One-pass form (var = E[x^2] - E[x]^2): both reductions read
            # x once and XLA fuses them into a single multi-output reduce
            # over the conv output — the two-pass (x - mean)^2 form
            # materializes the centered activations and dominated the
            # ResNet step (the conv MXU work is the minority of the time).
            x32 = x.astype(jnp.promote_types(x.dtype, jnp.float32))
            mean = jnp.mean(x32, axis=axes)
            meansq = jnp.mean(jnp.square(x32), axis=axes)
            var = jnp.maximum(meansq - jnp.square(mean), 0.0)
            m = self.momentum
            new_mean = moving_mean * m + jax.lax.stop_gradient(
                mean.astype(moving_mean.dtype)) * (1 - m)
            new_var = moving_var * m + jax.lax.stop_gradient(
                var.astype(moving_var.dtype)) * (1 - m)
            new_aux = [new_mean, new_var]
        else:
            mean = jax.lax.stop_gradient(moving_mean)
            var = jax.lax.stop_gradient(moving_var)
            new_aux = [moving_mean, moving_var]
        # fold the affine into one per-channel scale/shift applied to x in
        # its own dtype: a single fused multiply-add pass instead of
        # subtract/normalize/scale/shift chains
        inv = jax.lax.rsqrt(var + self.eps)
        scale = (gamma.astype(inv.dtype) * inv).astype(x.dtype)
        shift = (beta.astype(inv.dtype) - mean * gamma.astype(inv.dtype)
                 * inv).astype(x.dtype)
        out = x * scale.reshape(bshape) + shift.reshape(bshape)
        return [out], new_aux


# ---------------------------------------------------------------------------
# Dropout (reference dropout-inl.h)
# ---------------------------------------------------------------------------
@register_op("Dropout")
class Dropout(Operator):
    name_hint = "dropout"
    PARAMS = {"p": Param(float, 0.5)}

    def apply(self, ctx, inputs, aux):
        x = inputs[0]
        if not ctx.is_train or self.p <= 0.0 or ctx.rng is None:
            return [x], []
        jax = _jax()
        keep = 1.0 - self.p
        mask = jax.random.bernoulli(ctx.rng, keep, x.shape)
        return [_jnp().where(mask, x / keep, 0.0).astype(x.dtype)], []


# ---------------------------------------------------------------------------
# Softmax output + friends (reference softmax_output-inl.h)
# ---------------------------------------------------------------------------
def _softmax(x, axis):
    return _jax().nn.softmax(x, axis=axis)


@register_op("SoftmaxOutput", aliases=["Softmax"])
class SoftmaxOutput(Operator):
    """Fused softmax + cross-entropy gradient: forward is softmax(data);
    backward is (softmax - one_hot(label)) * grad_scale, ignoring the head
    gradient (reference softmax_output-inl.h; this is why MXNet training
    loops call ``backward()`` with no head grads)."""

    name_hint = "softmax"
    PARAMS = {
        "grad_scale": Param(float, 1.0),
        "ignore_label": Param(float, -1.0),
        "multi_output": Param(bool, False),
        "use_ignore": Param(bool, False),
        "preserve_shape": Param(bool, False,
                                "softmax over the last axis of an N-d "
                                "input with (shape[:-1]) labels"),
        "normalization": Param(str, "null", "null/batch/valid"),
    }

    def list_arguments(self):
        return ["data", "label"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("SoftmaxOutput: data shape unknown")
        if self.multi_output:
            label = (data[0],) + tuple(data[2:])
        elif self.preserve_shape:
            # reference softmax_output-inl.h preserve_shape: softmax on
            # the trailing axis, one label per leading position (the
            # time-major RNN head: data (T, N, V), label (T, N))
            label = tuple(data[:-1])
        else:
            label = (data[0],)
        return [data, label], [data], []

    def apply(self, ctx, inputs, aux):
        jax = _jax()
        jnp = _jnp()
        axis = 1 if self.multi_output else -1
        nclass_axis = 1 if self.multi_output else len(inputs[0].shape) - 1
        op = self

        @jax.custom_vjp
        def f(data, label):
            return _softmax(data, axis)

        def f_fwd(data, label):
            out = _softmax(data, axis)
            return out, (out, label)

        def f_bwd(res, g):
            out, label = res
            nclass = out.shape[nclass_axis]
            lab = label.astype(jnp.int32)
            onehot = jax.nn.one_hot(lab, nclass, dtype=out.dtype,
                                    axis=nclass_axis)
            grad = out - onehot
            valid = None
            if op.use_ignore:
                valid = (label != op.ignore_label)
                mask = jnp.expand_dims(valid, nclass_axis).astype(out.dtype)
                grad = grad * mask
            scale = op.grad_scale
            if op.normalization == "batch":
                grad = grad / out.shape[0]
            elif op.normalization == "valid":
                if valid is None:
                    valid = jnp.ones(label.shape, dtype=bool)
                grad = grad / jnp.maximum(jnp.sum(valid.astype(out.dtype)), 1.0)
            grad = grad * scale
            return grad.astype(out.dtype), jnp.zeros_like(label)

        f.defvjp(f_fwd, f_bwd)
        return [f(inputs[0], inputs[1])], []


@register_op("SoftmaxActivation")
class SoftmaxActivation(Operator):
    """Plain softmax with true autodiff gradient (reference
    softmax_activation-inl.h)."""

    name_hint = "softmaxactivation"
    PARAMS = {"mode": Param(str, "instance", "instance/channel")}

    def apply(self, ctx, inputs, aux):
        axis = 1 if self.mode == "channel" else -1
        return [_softmax(inputs[0], axis)], []


class _RegressionOutput(Operator):
    """Base for regression outputs (reference regression_output-inl.h):
    forward transforms data, backward is (out - label) * grad_scale / batch
    regardless of head gradient."""

    PARAMS = {"grad_scale": Param(float, 1.0)}
    transform = staticmethod(lambda x: x)
    grad_fn = staticmethod(lambda out, label: out - label)

    def list_arguments(self):
        return ["data", "label"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("%s: data shape unknown" % type(self).__name__)
        return [data, data], [data], []

    def apply(self, ctx, inputs, aux):
        jax = _jax()
        jnp = _jnp()
        op = self

        @jax.custom_vjp
        def f(data, label):
            return op.transform(data)

        def f_fwd(data, label):
            out = op.transform(data)
            return out, (out, label)

        def f_bwd(res, g):
            out, label = res
            label = label.reshape(out.shape)
            num = float(np.prod(out.shape[1:])) or 1.0
            grad = op.grad_fn(out, label) * (op.grad_scale / num)
            return grad.astype(out.dtype), jnp.zeros_like(label)

        f.defvjp(f_fwd, f_bwd)
        return [f(inputs[0], inputs[1])], []


@register_op("LinearRegressionOutput")
class LinearRegressionOutput(_RegressionOutput):
    name_hint = "linearregressionoutput"


@register_op("LogisticRegressionOutput")
class LogisticRegressionOutput(_RegressionOutput):
    name_hint = "logisticregressionoutput"
    transform = staticmethod(lambda x: _jax().nn.sigmoid(x))


@register_op("MAERegressionOutput")
class MAERegressionOutput(_RegressionOutput):
    name_hint = "maeregressionoutput"
    grad_fn = staticmethod(lambda out, label: _jnp().sign(out - label))


@register_op("SVMOutput")
class SVMOutput(Operator):
    """reference svmoutput-inl.h: hinge-loss output layer."""

    name_hint = "svmoutput"
    PARAMS = {
        "margin": Param(float, 1.0),
        "regularization_coefficient": Param(float, 1.0),
        "use_linear": Param(bool, False),
    }

    def list_arguments(self):
        return ["data", "label"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("SVMOutput: data shape unknown")
        return [data, (data[0],)], [data], []

    def apply(self, ctx, inputs, aux):
        jax = _jax()
        jnp = _jnp()
        op = self

        @jax.custom_vjp
        def f(data, label):
            return data

        def f_fwd(data, label):
            return data, (data, label)

        def f_bwd(res, g):
            data, label = res
            lab = label.astype(jnp.int32)
            onehot = jax.nn.one_hot(lab, data.shape[1], dtype=data.dtype)
            sign = 2.0 * onehot - 1.0          # +1 at true class, -1 elsewhere
            viol = (op.margin - sign * data) > 0
            if op.use_linear:
                grad = -sign * viol.astype(data.dtype)
            else:
                grad = -2.0 * sign * jnp.maximum(op.margin - sign * data, 0.0)
            grad = grad * op.regularization_coefficient
            return grad.astype(data.dtype), jnp.zeros_like(label)

        f.defvjp(f_fwd, f_bwd)
        return [f(inputs[0], inputs[1])], []


# ---------------------------------------------------------------------------
# Embedding (reference embedding-inl.h)
# ---------------------------------------------------------------------------
@register_op("Embedding")
class Embedding(Operator):
    name_hint = "embedding"
    PARAMS = {
        "input_dim": Param(int, REQUIRED),
        "output_dim": Param(int, REQUIRED),
    }
    # ids above 255 do not survive a cast to bfloat16
    full_precision_args = ("data",)

    def list_arguments(self):
        return ["data", "weight"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("Embedding: data shape unknown")
        return ([data, (self.input_dim, self.output_dim)],
                [tuple(data) + (self.output_dim,)], [])

    def infer_type(self, in_types, out_types=None):
        # indices keep their own dtype (often int); weight/output share a
        # float dtype and must NOT inherit the index dtype. No speculative
        # float32 — an unknown weight stays None until the symbol-level
        # default pass (it is a plain variable there).
        data_t, weight_t = in_types
        out_t = (out_types or [None])[0]
        w = weight_t if weight_t is not None else out_t
        return [data_t, w], [w], []

    def apply(self, ctx, inputs, aux):
        jnp = _jnp()
        data, weight = inputs
        idx = _jax().lax.stop_gradient(data).astype(jnp.int32)
        return [jnp.take(weight, idx, axis=0)], []


# ---------------------------------------------------------------------------
# Normalization ops
# ---------------------------------------------------------------------------
@register_op("RMSNorm")
class RMSNorm(Operator):
    """Root-mean-square normalisation over the last axis (Zhang and
    Sennrich, arXiv:1910.07467): ``x * rsqrt(mean(x^2) + eps) * gamma``,
    statistics in float32 whatever the compute dtype.

    ``num_groups`` > 1 normalises each of that many equal slices of the
    last axis on its own, under one learned ``gamma`` over the whole width
    or, with ``shared_gamma``, ONE ``gamma`` of a slice's width that every
    slice shares (a head's norm). ``gated=True`` takes a second input
    ``gate``: by default it normalises ``x * silu(gate)``, the gated norm
    that closes a Mamba-2 mixer (Dao and Gu, arXiv:2405.21060); with
    ``gate_after`` it normalises ``x`` first and multiplies by
    ``silu(gate)`` after, as a gated delta-rule mixer closes (Yang et al.,
    arXiv:2412.06464)."""

    name_hint = "rmsnorm"
    PARAMS = {
        "eps": Param(float, 1e-5),
        "num_groups": Param(int, 1),
        "gated": Param(bool, False),
        "gate_after": Param(bool, False, "norm(x) * silu(gate), not "
                            "norm(x * silu(gate))"),
        "shared_gamma": Param(bool, False, "one gamma of a group's width"),
    }

    def list_arguments(self):
        return ["data", "gamma", "gate"] if self.gated \
            else ["data", "gamma"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("RMSNorm: data shape unknown")
        if data[-1] % self.num_groups:
            raise MXNetError("RMSNorm: width %d is not %d equal groups"
                             % (data[-1], self.num_groups))
        if self.gate_after and not self.gated:
            raise MXNetError("RMSNorm: gate_after without gated")
        width = data[-1] // self.num_groups if self.shared_gamma \
            else data[-1]
        shapes = [data, (width,)] + ([data] if self.gated else [])
        return shapes, [data], []

    def apply(self, ctx, inputs, aux):
        jax, jnp = _jax(), _jnp()
        x, gamma = inputs[0], inputs[1]
        y = x.astype(jnp.float32)
        gate = jax.nn.silu(inputs[2].astype(jnp.float32)) if self.gated \
            else None
        if self.gated and not self.gate_after:
            y = y * gate
        g = self.num_groups
        yg = y.reshape(y.shape[:-1] + (g, y.shape[-1] // g))
        yg = yg * jax.lax.rsqrt(
            jnp.mean(jnp.square(yg), axis=-1, keepdims=True) + self.eps)
        if self.shared_gamma:
            y = (yg * gamma.astype(jnp.float32)).reshape(y.shape)
        else:
            y = yg.reshape(y.shape) * gamma.astype(jnp.float32)
        if self.gate_after:
            y = y * gate
        return [y.astype(x.dtype)], []


@register_op("LRN")
class LRN(Operator):
    """Cross-channel local response normalization (reference lrn-inl.h)."""

    name_hint = "lrn"
    PARAMS = {
        "alpha": Param(float, 1e-4),
        "beta": Param(float, 0.75),
        "knorm": Param(float, 2.0),
        "nsize": Param(int, REQUIRED),
    }

    def apply(self, ctx, inputs, aux):
        lax = _jax().lax
        x = inputs[0]
        half = self.nsize // 2
        sq = x * x
        window = (1, self.nsize) + (1,) * (x.ndim - 2)
        padding = ((0, 0), (half, self.nsize - 1 - half)) + ((0, 0),) * (x.ndim - 2)
        ssum = lax.reduce_window(sq, 0.0, lax.add, window, (1,) * x.ndim, padding)
        denom = (self.knorm + (self.alpha / self.nsize) * ssum) ** self.beta
        return [x / denom], []


@register_op("L2Normalization")
class L2Normalization(Operator):
    """reference l2_normalization-inl.h (mode=instance/channel/spatial)."""

    name_hint = "l2normalization"
    PARAMS = {
        "eps": Param(float, 1e-10),
        "mode": Param(str, "instance"),
    }

    def apply(self, ctx, inputs, aux):
        jnp = _jnp()
        x = inputs[0]
        if self.mode == "instance":
            axes = tuple(range(1, x.ndim))
        elif self.mode == "channel":
            axes = (1,)
        elif self.mode == "spatial":
            axes = tuple(range(2, x.ndim))
        else:
            raise MXNetError("unknown mode %s" % self.mode)
        norm = jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True) + self.eps)
        return [x / norm], []


# ---------------------------------------------------------------------------
# UpSampling (reference upsampling-inl.h; nearest only — bilinear is a
# Deconvolution in the reference too)
# ---------------------------------------------------------------------------
@register_op("UpSampling")
class UpSampling(Operator):
    name_hint = "upsampling"
    PARAMS = {
        "scale": Param(int, REQUIRED),
        "sample_type": Param(str, "nearest"),
        "num_args": Param(int, 1),
    }

    def list_arguments(self):
        return ["data"] if self.num_args == 1 else \
            ["arg%d" % i for i in range(self.num_args)]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("UpSampling: data shape unknown")
        out = data[:2] + tuple(s * self.scale for s in data[2:])
        return [data], [out], []

    def apply(self, ctx, inputs, aux):
        jnp = _jnp()
        x = inputs[0]
        for ax in range(2, x.ndim):
            x = jnp.repeat(x, self.scale, axis=ax)
        return [x], []
