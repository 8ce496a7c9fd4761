"""Sequence operators + fused RNN.

TPU-native equivalents of the reference's sequence ops
(``src/operator/sequence_{last,mask,reverse}-inl.h``) and of the cuDNN fused
RNN (``src/operator/cudnn_rnn-inl.h:127-150``: RNN_RELU/RNN_TANH/LSTM/GRU).
The recurrence is a ``jax.lax.scan`` over time with one fused cell matmul
per step — the XLA-idiomatic formulation: weights stay resident in
registers/VMEM across iterations and the (x,h)->gates matmul hits the MXU.

Layout is time-major TNC like the reference RNN op. Parameters are a single
flat vector like cuDNN blobs; layout is documented in :func:`rnn_param_size`
(per layer/direction: W_x, W_h, b_x, b_h, gates in cuDNN order).
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from ..base import MXNetError
from .pallas_kernels import DELTA_SUB_CHUNK
from .registry import Operator, Param, REQUIRED, register_op


def _jax():
    import jax
    return jax


def _jnp():
    import jax.numpy as jnp
    return jnp


# ---------------------------------------------------------------------------
# sequence_* ops: per-example lengths along time-major axis
# ---------------------------------------------------------------------------
class _SeqBase(Operator):
    PARAMS = {"use_sequence_length": Param(bool, False)}

    def list_arguments(self):
        if self.use_sequence_length:
            return ["data", "sequence_length"]
        return ["data"]


@register_op("SequenceLast")
class SequenceLast(_SeqBase):
    name_hint = "sequencelast"

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("SequenceLast: data shape unknown")
        shapes = [data]
        if self.use_sequence_length:
            shapes.append((data[1],))
        return shapes, [tuple(data[1:])], []

    def apply(self, ctx, inputs, aux):
        jnp = _jnp()
        x = inputs[0]
        if self.use_sequence_length:
            idx = (inputs[1].astype(jnp.int32) - 1).clip(0, x.shape[0] - 1)
            return [x[idx, jnp.arange(x.shape[1])]], []
        return [x[-1]], []


@register_op("SequenceMask")
class SequenceMask(_SeqBase):
    name_hint = "sequencemask"
    PARAMS = dict(_SeqBase.PARAMS, value=Param(float, 0.0))

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("SequenceMask: data shape unknown")
        shapes = [data]
        if self.use_sequence_length:
            shapes.append((data[1],))
        return shapes, [data], []

    def apply(self, ctx, inputs, aux):
        jnp = _jnp()
        x = inputs[0]
        if not self.use_sequence_length:
            return [x], []
        lengths = inputs[1].astype(jnp.int32)
        t = jnp.arange(x.shape[0])[:, None]
        mask = (t < lengths[None, :]).reshape(
            (x.shape[0], x.shape[1]) + (1,) * (x.ndim - 2))
        return [jnp.where(mask, x, jnp.asarray(self.value, x.dtype))], []


@register_op("SequenceReverse")
class SequenceReverse(_SeqBase):
    name_hint = "sequencereverse"

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("SequenceReverse: data shape unknown")
        shapes = [data]
        if self.use_sequence_length:
            shapes.append((data[1],))
        return shapes, [data], []

    def apply(self, ctx, inputs, aux):
        jnp = _jnp()
        x = inputs[0]
        if not self.use_sequence_length:
            return [x[::-1]], []
        lengths = inputs[1].astype(_jnp().int32)
        t = jnp.arange(x.shape[0])[:, None]
        # index of reversed element within each valid prefix
        src = jnp.where(t < lengths[None, :], lengths[None, :] - 1 - t, t)
        return [x[src, jnp.arange(x.shape[1])[None, :]]], []


# ---------------------------------------------------------------------------
# fused RNN (reference rnn-inl.h param struct :70-100 + cudnn_rnn-inl.h)
# ---------------------------------------------------------------------------
_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def rnn_param_size(num_layers: int, input_size: int, state_size: int,
                   bidirectional: bool, mode: str) -> int:
    """Total flat parameter count. Layout (contiguous, per layer then per
    direction): W_x (G*H, in), W_h (G*H, H), b_x (G*H), b_h (G*H)."""
    gates = _GATES[mode]
    dirs = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_size = input_size if layer == 0 else state_size * dirs
        size += dirs * gates * state_size * (in_size + state_size + 2)
    return size


@register_op("RNN")
class RNN(Operator):
    name_hint = "rnn"
    PARAMS = {
        "state_size": Param(int, REQUIRED),
        "num_layers": Param(int, REQUIRED),
        "mode": Param(str, REQUIRED, "rnn_relu/rnn_tanh/lstm/gru"),
        "bidirectional": Param(bool, False),
        "p": Param(float, 0.0, "dropout between layers"),
        "state_outputs": Param(bool, False),
    }

    def list_arguments(self):
        args = ["data", "parameters", "state"]
        if self.mode == "lstm":
            args.append("state_cell")
        return args

    def list_outputs(self):
        outs = ["output"]
        if self.state_outputs:
            outs.append("state")
            if self.mode == "lstm":
                outs.append("state_cell")
        return outs

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("RNN: data shape unknown")
        t, n, input_size = data
        dirs = 2 if self.bidirectional else 1
        h = self.state_size
        psize = rnn_param_size(self.num_layers, input_size, h,
                               self.bidirectional, self.mode)
        state_shape = (self.num_layers * dirs, n, h)
        shapes = [data, (psize,), state_shape]
        if self.mode == "lstm":
            shapes.append(state_shape)
        outs = [(t, n, h * dirs)]
        if self.state_outputs:
            outs.append(state_shape)
            if self.mode == "lstm":
                outs.append(state_shape)
        return shapes, outs, []

    # -- flat parameter unpacking ------------------------------------------
    def _slices(self, input_size):
        gates = _GATES[self.mode]
        dirs = 2 if self.bidirectional else 1
        h = self.state_size
        offset = 0
        layout = []  # [layer][dir] = dict of (offset, shape)
        for layer in range(self.num_layers):
            in_size = input_size if layer == 0 else h * dirs
            per_dir = []
            for _ in range(dirs):
                entry = {}
                for key, shape in (("wx", (gates * h, in_size)),
                                   ("wh", (gates * h, h)),
                                   ("bx", (gates * h,)),
                                   ("bh", (gates * h,))):
                    size = int(np.prod(shape))
                    entry[key] = (offset, shape)
                    offset += size
                per_dir.append(entry)
            layout.append(per_dir)
        return layout

    def _cell(self, mode):
        jnp = _jnp()
        jax = _jax()
        h_units = self.state_size

        if mode in ("rnn_relu", "rnn_tanh"):
            act = (lambda v: jnp.maximum(v, 0)) if mode == "rnn_relu" else jnp.tanh

            def cell(carry, xw, wh, bh):
                h_prev, = carry
                h = act(xw + jnp.dot(h_prev, wh.T) + bh)
                return (h,), h
        elif mode == "lstm":
            def cell(carry, xw, wh, bh):
                h_prev, c_prev = carry
                gates = xw + jnp.dot(h_prev, wh.T) + bh
                i, f, g, o = jnp.split(gates, 4, axis=-1)
                i = jax.nn.sigmoid(i)
                f = jax.nn.sigmoid(f)
                g = jnp.tanh(g)
                o = jax.nn.sigmoid(o)
                c = f * c_prev + i * g
                h = o * jnp.tanh(c)
                return (h, c), h
        elif mode == "gru":
            def cell(carry, xw, wh, bh):
                h_prev, = carry
                hw = jnp.dot(h_prev, wh.T) + bh
                xr, xz, xn = jnp.split(xw, 3, axis=-1)
                hr, hz, hn = jnp.split(hw, 3, axis=-1)
                r = jax.nn.sigmoid(xr + hr)
                z = jax.nn.sigmoid(xz + hz)
                n = jnp.tanh(xn + r * hn)
                h = (1 - z) * n + z * h_prev
                return (h,), h
        else:
            raise MXNetError("unknown RNN mode %s" % mode)
        return cell

    def apply(self, ctx, inputs, aux):
        jax = _jax()
        jnp = _jnp()
        data = inputs[0]
        params = inputs[1]
        state0 = inputs[2]
        cell0 = inputs[3] if self.mode == "lstm" else None
        t, n, input_size = data.shape
        dirs = 2 if self.bidirectional else 1
        layout = self._slices(input_size)
        cell = self._cell(self.mode)

        def take(off_shape):
            off, shape = off_shape
            return jax.lax.dynamic_slice_in_dim(
                params, off, int(np.prod(shape))).reshape(shape)

        x = data
        h_finals, c_finals = [], []
        for layer in range(self.num_layers):
            outs_dirs = []
            for d in range(dirs):
                entry = layout[layer][d]
                wx, wh = take(entry["wx"]), take(entry["wh"])
                bx, bh = take(entry["bx"]), take(entry["bh"])
                sidx = layer * dirs + d
                h0 = state0[sidx]
                carry = (h0, cell0[sidx]) if self.mode == "lstm" else (h0,)
                seq = x if d == 0 else x[::-1]
                # hoist the input projection out of the scan: one big
                # (T*N, in) x (in, G*H) matmul for the MXU
                xw_all = jnp.einsum("tni,gi->tng", seq, wx) + bx

                def step(carry, xw, _wh=wh, _bh=bh):
                    new_carry, h = cell(carry, xw, _wh, _bh)
                    return new_carry, h

                final, hs = jax.lax.scan(step, carry, xw_all)
                if d == 1:
                    hs = hs[::-1]
                outs_dirs.append(hs)
                h_finals.append(final[0])
                if self.mode == "lstm":
                    c_finals.append(final[1])
            x = outs_dirs[0] if dirs == 1 else jnp.concatenate(outs_dirs, axis=-1)
            if self.p > 0 and ctx.is_train and ctx.rng is not None \
                    and layer < self.num_layers - 1:
                keep = 1.0 - self.p
                key = jax.random.fold_in(ctx.rng, layer)
                mask = jax.random.bernoulli(key, keep, x.shape)
                x = jnp.where(mask, x / keep, 0.0).astype(x.dtype)

        outputs = [x]
        if self.state_outputs:
            outputs.append(jnp.stack(h_finals))
            if self.mode == "lstm":
                outputs.append(jnp.stack(c_finals))
        return outputs, []


# ---------------------------------------------------------------------------
# state-space layers: causal depthwise conv + chunked selective scan
# (Mamba-2: Dao and Gu, "Transformers are SSMs", arXiv:2405.21060)
# ---------------------------------------------------------------------------
#
# Layout for the language-model ops (these two, ``CausalAttention`` and
# ``RoutedExperts``): activations are 2-D ``[rows, width]`` as
# ``FullyConnected`` produces them, the rows being whole sequences of
# ``seq_len`` positions laid end to end; an op that needs the sequence
# reads ``seq_len`` from its Symbol parameters and the batch from the
# shape.
def _sequences(rows, seq_len, what):
    if seq_len <= 0 or rows % seq_len:
        raise MXNetError("%s: %d rows are not whole sequences of %d"
                         % (what, rows, seq_len))
    return rows // seq_len


@register_op("CausalConv1D")
class CausalConv1D(Operator):
    """Depthwise causal convolution along each sequence:
    ``out[t, c] = bias[c] + sum_k weight[c, k] * x[t - (K-1) + k, c]``
    with zeros before the sequence's start (a PyTorch ``Conv1d`` with
    ``groups=C, padding=K-1`` cut to the first T outputs). K shifted
    multiply-adds that XLA fuses into one pass; accumulated in float32."""

    name_hint = "causalconv1d"
    PARAMS = {
        "kernel": Param(int, REQUIRED),
        "seq_len": Param(int, REQUIRED),
        "no_bias": Param(bool, False),
    }

    def list_arguments(self):
        return ["data", "weight"] if self.no_bias \
            else ["data", "weight", "bias"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("CausalConv1D: data shape unknown")
        _sequences(data[0], self.seq_len, "CausalConv1D")
        shapes = [data, (data[1], self.kernel)]
        if not self.no_bias:
            shapes.append((data[1],))
        return shapes, [data], []

    def apply(self, ctx, inputs, aux):
        jnp = _jnp()
        x, w = inputs[0], inputs[1].astype(jnp.float32)
        rows, c = x.shape
        t, k = self.seq_len, self.kernel
        xs = jnp.pad(x.reshape(rows // t, t, c), ((0, 0), (k - 1, 0), (0, 0)))
        out = sum(xs[:, i:i + t].astype(jnp.float32) * w[:, i]
                  for i in range(k))
        if not self.no_bias:
            out = out + inputs[2].astype(jnp.float32)
        return [out.reshape(rows, c).astype(x.dtype)], []


@register_op("GatedShortConv")
class GatedShortConv(Operator):
    """The gated short convolution of the LFM2 family's ``conv`` mixers,
    between its two projections: ``data [rows, 3C]`` holds ``[B; C; z]``,
    three equal chunks in that order, and ``out = C * conv(B * z)``, ``conv``
    the depthwise causal convolution of :class:`CausalConv1D` over the ``C``
    channels (kernel ``kernel``, zeros before the sequence's start, no bias,
    NO activation anywhere). One node, so that the two gates and the K
    shifted multiply-adds are one float32 expression with one rounding that
    XLA fuses into a pass over ``data`` (counted
    ``lower.shortconv_body.xla_fused``); as plain ``slice_axis`` / ``_Mul``
    / ``CausalConv1D`` nodes the gated product and the convolution's result
    would each be rounded to the compute dtype and written out."""

    name_hint = "gatedshortconv"
    PARAMS = {
        "kernel": Param(int, REQUIRED),
        "seq_len": Param(int, REQUIRED),
    }

    def list_arguments(self):
        return ["data", "weight"]

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("GatedShortConv: data shape unknown")
        if data[1] % 3:
            raise MXNetError("GatedShortConv: width %d is not three equal "
                             "chunks" % data[1])
        _sequences(data[0], self.seq_len, "GatedShortConv")
        c = data[1] // 3
        return [data, (c, self.kernel)], [(data[0], c)], []

    def remat_results(self, in_shapes, in_types):
        """The result: two gates and K multiply-adds a value to run again
        against one activation to hold, so the last of a plan's choices."""
        rows, c = in_shapes[0][0], in_shapes[0][1] // 3
        return [("output", rows * c * np.dtype(in_types[0]).itemsize,
                 (2 * self.kernel + 2) * rows * c)]

    def apply(self, ctx, inputs, aux):
        jnp = _jnp()
        from .. import telemetry as _tel

        _tel.inc("lower.shortconv_body.xla_fused")
        x, w = inputs[0], inputs[1].astype(jnp.float32)
        rows, c = x.shape[0], x.shape[1] // 3
        t, k = self.seq_len, self.kernel
        b, gate, z = (x[:, i * c:(i + 1) * c].astype(jnp.float32).reshape(
            rows // t, t, c) for i in range(3))
        vs = jnp.pad(b * z, ((0, 0), (k - 1, 0), (0, 0)))
        out = gate * sum(vs[:, i:i + t] * w[:, i] for i in range(k))
        return [ctx.keep(out.reshape(rows, c).astype(x.dtype), "output")], []


def _ssd_chunks(x, dt, a_head, b_mat, c_mat, chunk):
    """What both passes of the XLA body share: the inputs cut into chunks
    ``[nc, L, ...]``, the cumulative decay inside each chunk, the masked
    decay matrix and the quadratic form's matrix ``(C B^T) * decay``."""
    jnp = _jnp()
    f32 = jnp.float32
    h, p = x.shape[1:]
    g, n = b_mat.shape[1:]
    hg = h // g
    nc = x.shape[0] // chunk
    xs = x.reshape(nc, chunk, g, hg, p)
    bs = b_mat.reshape(nc, chunk, g, n)
    cs_ = c_mat.reshape(nc, chunk, g, n)
    dts = dt.reshape(nc, chunk, g, hg)
    cum = jnp.cumsum(dts * a_head.reshape(g, hg), axis=1)   # [nc, L, g, hg]
    cb = jnp.einsum("ctgn,csgn->cgts", cs_, bs, preferred_element_type=f32)
    cum_h = cum.transpose(0, 2, 3, 1)                       # [nc, g, hg, L]
    seg = cum_h[..., :, None] - cum_h[..., None, :]         # [.., t, s]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    # the step rides on x, so one matrix serves y = m (dt x) and its mirror
    xdt = (xs.astype(f32) * dts[..., None]).astype(x.dtype)
    m = (cb[:, :, None] * decay).astype(x.dtype)
    return xs, bs, cs_, dts, cum, decay, cb, xdt, m


def ssd_chunked(x, dt, a_head, d_skip, b_mat, c_mat, chunk):
    """One sequence of the selective state-space recurrence
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D
    x_t`` (per head; S is ``[P, N]``), computed by chunks of ``chunk``
    positions: inside a chunk the quadratic form (a masked ``[chunk,
    chunk]`` decay matrix times ``C B^T``), between chunks the carried
    state. Decays, cumulative sums and the carried state are float32; the
    matrix products take their inputs in ``x.dtype`` and accumulate in
    float32; ``y`` is rounded to ``x.dtype`` once, after the skip.

    ``x [T, H, P]`` (T whole chunks), ``dt [T, H]`` (after softplus,
    float32), ``a_head [H]`` (negative, float32), ``d_skip [H]``,
    ``b_mat``/``c_mat [T, G, N]``; head h reads group ``h // (H/G)``.
    Returns ``y [T, H, P]`` and the float32 state at each chunk's start
    ``[T/chunk, G, H/G, P, N]``: all that :func:`ssd_chunked_grad` needs
    beside the inputs. Nothing here is ``[T, T]`` and no per-position
    state exists."""
    jax, jnp = _jax(), _jnp()
    f32 = jnp.float32
    t, h, p = x.shape
    g, n = b_mat.shape[1:]
    cd = x.dtype
    xs, bs, cs_, dts, cum, _, _, xdt, m = _ssd_chunks(x, dt, a_head, b_mat,
                                                      c_mat, chunk)
    # inside the chunk: y_t += sum_{s<=t} exp(cum_t - cum_s) (C_t.B_s) dt_s x_s
    y = jnp.einsum("cghts,csghp->ctghp", m, xdt, preferred_element_type=f32)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, -1:] - cum) * dts               # [nc, L, g, hg]
    xw = (xs.astype(f32) * to_end[..., None]).astype(cd)
    states = jnp.einsum("csghp,csgn->cghpn", xw, bs,
                        preferred_element_type=f32)
    # between chunks: the carried state, float32, one step a chunk

    def carry(s, inp):
        d, st = inp
        return d[..., None, None] * s + st, s               # emits the START

    _, starts = jax.lax.scan(carry, jnp.zeros((g, h // g, p, n), f32),
                             (jnp.exp(cum[:, -1]), states))
    y = y + jnp.einsum("ctgn,cghpn->ctghp", cs_, starts.astype(cd),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y.reshape(t, h, p) + d_skip[:, None] * x.astype(f32)
    return y.astype(cd), starts


def ssd_chunked_grad(x, dt, a_head, d_skip, b_mat, c_mat, starts, dy, chunk):
    """The backward pass of :func:`ssd_chunked`, written out. The chunk's
    matrices are formed again from the inputs, the state's gradient ``[P,
    N]`` is carried in float32 from the last chunk to the first, and every
    product mirrors one of the forward pass (inputs in ``x.dtype``,
    float32 accumulation). Returns ``dx, dB, dC`` in ``x.dtype`` and, in
    float32 ``[T, H]``, the gradient of each position's cumulative decay
    and of its step where the step scales ``x``
    (:func:`_ssd_step_grads` makes ``d dt`` and ``dA`` of the two).

    The decay's gradient inside a chunk is ONE float32 matrix ``dM * M``
    summed along its rows (at t) and along its columns (at s, negative):
    the same number on both sides, so what cancels cancels exactly. (The
    shorter ``<dy_t, y_t> - <x_t, dx_t>`` forms the two sides from
    differently rounded products, and the difference drifts along the
    sequence.)"""
    jax, jnp = _jax(), _jnp()
    f32 = jnp.float32
    t, h, p = x.shape
    g, n = b_mat.shape[1:]
    cd = x.dtype
    xs, bs, cs_, dts, cum, decay, cb, xdt, m = _ssd_chunks(
        x, dt, a_head, b_mat, c_mat, chunk)
    gs = dy.reshape(xs.shape)
    gf, xf = gs.astype(f32), xs.astype(f32)
    ecum = jnp.exp(cum)
    wexp = jnp.exp(cum[:, -1:] - cum)
    # the state's gradient: what reaches a chunk's END from the chunks after
    dyw = (gf * ecum[..., None]).astype(cd)
    local = jnp.einsum("ctghp,ctgn->cghpn", dyw, cs_,
                       preferred_element_type=f32)

    def carry(ds, inp):
        d, dl = inp
        return d[..., None, None] * ds + dl, ds

    _, ds_end = jax.lax.scan(carry, jnp.zeros(starts.shape[1:], f32),
                             (jnp.exp(cum[:, -1]), local), reverse=True)
    # the decay carries the whole state over a chunk: <dS_end, S_start>
    at_end = jnp.exp(cum[:, -1]) * jnp.sum(ds_end * starts, axis=(-2, -1))
    ds_end, st0 = ds_end.astype(cd), starts.astype(cd)
    from_start = jnp.einsum("ctgn,cghpn->ctghp", cs_, st0,
                            preferred_element_type=f32)
    from_end = jnp.einsum("csgn,cghpn->csghp", bs, ds_end,
                          preferred_element_type=f32)
    dxs = jnp.einsum("cghts,ctghp->csghp", m, gs,
                     preferred_element_type=f32) + from_end * wexp[..., None]
    dm = jnp.einsum("ctghp,csghp->cghts", gs, xdt,
                    preferred_element_type=f32)
    dcb = jnp.sum(dm * decay, axis=2).astype(cd)            # [nc, g, t, s]
    xw = (xf * (wexp * dts)[..., None]).astype(cd)
    dc = jnp.einsum("cgts,csgn->ctgn", dcb, bs, preferred_element_type=f32) \
        + jnp.einsum("ctghp,cghpn->ctgn", dyw, st0,
                     preferred_element_type=f32)
    db = jnp.einsum("cgts,ctgn->csgn", dcb, cs_, preferred_element_type=f32) \
        + jnp.einsum("csghp,cghpn->csgn", xw, ds_end,
                     preferred_element_type=f32)
    e = dm * (cb[:, :, None] * decay)                       # [nc, g, hg, t, s]
    to_end = jnp.sum(xf * from_end, axis=-1) * wexp * dts   # [nc, L, g, hg]
    dcum = (jnp.sum(e, axis=-1) - jnp.sum(e, axis=-2)).transpose(0, 3, 1, 2) \
        + jnp.sum(gf * from_start, axis=-1) * ecum - to_end
    dcum = dcum.at[:, -1].add(jnp.sum(to_end, axis=1) + at_end)
    dx = dts[..., None] * dxs + d_skip.reshape(g, h // g, 1) * gf
    return (dx.reshape(t, h, p).astype(cd), db.reshape(t, g, n).astype(cd),
            dc.reshape(t, g, n).astype(cd), dcum.reshape(t, h),
            jnp.sum(xf * dxs, axis=-1).reshape(t, h))


def _ssd_step_grads(dt, a_head, dcum, dstep, chunk):
    """``d dt [B, T, H]`` and ``dA [H]`` from the backward pass's two
    sums: a step enters the cumulative decay of every later position of
    its chunk, and scales ``x``."""
    jax, jnp = _jax(), _jnp()
    b, t, h = dt.shape
    da = jax.lax.cumsum(dcum.reshape(b, t // chunk, chunk, h), axis=2,
                        reverse=True).reshape(b, t, h)
    return dstep + da * a_head, jnp.sum(da * dt, axis=(0, 1))


def ssd_scan(xbc, dt, a_head, d_skip, dims, chunk, kernel):
    """:func:`ssd_chunked` over sequences as ONE differentiable function
    with both passes written out. ``xbc [B, T, H*P + 2*G*N]`` holds ``x |
    B | C`` side by side as ``SSMScan`` gets them (``dims = (H, P, G,
    N)``; T whole chunks), ``dt [B, T, H]``; returns ``y [B, T, H*P]``.
    ``kernel`` picks the body: the Pallas chunk kernel
    (``pallas_kernels.ssd_chunk_forward`` / ``_backward``: the decay
    matrices and the carried state stay in VMEM, and the three parts are
    read where they lie) or the same algorithm in ``jax.numpy``, one
    sequence at a time. The residuals are the inputs and the float32
    chunk-start states."""
    jax, jnp = _jax(), _jnp()
    from . import pallas_kernels

    h, p, g, n = dims
    di, gn = h * p, g * n

    def parts(xbc):
        lead = xbc.shape[:-1]
        return (xbc[..., :di].reshape(lead + (h, p)),
                xbc[..., di:di + gn].reshape(lead + (g, n)),
                xbc[..., di + gn:].reshape(lead + (g, n)))

    def run(with_states, xbc, dt, a_head, d_skip):
        if kernel:
            return pallas_kernels.ssd_chunk_forward(
                xbc, dt, a_head, d_skip, dims=dims, chunk=chunk,
                with_states=with_states)

        def one(v):
            x, b_mat, c_mat = parts(v[0])
            y, starts = ssd_chunked(x, v[1], a_head, d_skip, b_mat, c_mat,
                                    chunk)
            return y.reshape(-1, di), starts

        return jax.lax.map(one, (xbc, dt))

    @jax.custom_vjp
    def f(*args):
        return run(False, *args)[0]

    def f_fwd(*args):
        y, starts = run(True, *args)
        return y, args + (starts,)

    def f_bwd(res, dy):
        xbc, dt, a_head, d_skip, starts = res
        if kernel:
            dx, db, dc, dcum, dstep = pallas_kernels.ssd_chunk_backward(
                *res, dy, dims=dims, chunk=chunk)
        else:
            def one(v):
                x, b_mat, c_mat = parts(v[0])
                dx, db, dc, dcum, dstep = ssd_chunked_grad(
                    x, v[1], a_head, d_skip, b_mat, c_mat, v[2],
                    v[3].reshape(x.shape), chunk)
                return (dx.reshape(-1, di), db.reshape(-1, gn),
                        dc.reshape(-1, gn), dcum, dstep)

            dx, db, dc, dcum, dstep = jax.lax.map(one, (xbc, dt, starts, dy))
        ddt, da = _ssd_step_grads(dt, a_head, dcum, dstep, chunk)
        dd = jnp.sum((dy.astype(jnp.float32)
                      * xbc[..., :di].astype(jnp.float32)).reshape(-1, h, p),
                     axis=(0, 2))
        return jnp.concatenate([dx, db, dc], axis=-1), ddt, da, dd

    f.defvjp(f_fwd, f_bwd)
    return f(xbc, dt, a_head, d_skip)


@register_op("SSMScan")
class SSMScan(Operator):
    """The selective state-space scan of a Mamba-2 mixer over whole
    sequences, chunked (:func:`ssd_chunked`), beside the LSTM's fused scan
    above. ``data`` is ``[rows, H*P + 2*G*N]``: per position ``x`` (H heads
    of P), ``B`` and ``C`` (G groups of N), as the mixer's convolution
    leaves them; ``dt`` is ``[rows, H]`` before its bias and softplus.
    ``y = scan(x, softplus(dt + dt_bias), -exp(A_log), B, C) + D * x``.

    Both passes are written out (:func:`ssd_scan`): the backward pass
    forms each chunk's matrices again and keeps only the inputs and the
    float32 chunk-start states, so memory is linear in ``seq_len`` and
    under segment recomputation the scan runs forward, forward, backward.
    The body is chosen from the shapes when the node is traced, as
    ``CausalAttention`` chooses: one Pallas chunk kernel where ``chunk``,
    ``state_size`` and the heads are whole tiles
    (``lower.scan_kernel.pallas_chunked``), else the same algorithm in
    ``jax.numpy`` (``lower.scan_kernel.xla_chunked``). In both, decays,
    cumulative sums, the carried state, its gradient and every accumulator
    are float32; the matrix products take their inputs in the compute
    dtype."""

    name_hint = "ssmscan"
    PARAMS = {
        "num_heads": Param(int, REQUIRED),
        "head_dim": Param(int, REQUIRED),
        "num_groups": Param(int, REQUIRED),
        "state_size": Param(int, REQUIRED),
        "chunk": Param(int, 128),
        "seq_len": Param(int, REQUIRED),
    }
    # the decay exp(-exp(A_log) dt) compounds over a sequence: its
    # parameters stay float32 (as the published kernels keep them)
    full_precision_args = ("A_log", "D", "dt_bias")

    def list_arguments(self):
        return ["data", "dt", "A_log", "D", "dt_bias"]

    def _widths(self):
        return (self.num_heads * self.head_dim,
                self.num_groups * self.state_size)

    def infer_shape(self, in_shapes):
        data = in_shapes[0]
        if data is None:
            raise MXNetError("SSMScan: data shape unknown")
        di, gn = self._widths()
        if data[1] != di + 2 * gn or self.num_heads % self.num_groups:
            raise MXNetError("SSMScan: data width %d is not x|B|C = %d + 2*%d"
                             % (data[1], di, gn))
        _sequences(data[0], self.seq_len, "SSMScan")
        heads = (self.num_heads,)
        return ([data, (data[0], self.num_heads), heads, heads, heads],
                [(data[0], di)], [])

    def apply(self, ctx, inputs, aux):
        jax, jnp = _jax(), _jnp()
        from .. import telemetry as _tel
        from . import pallas_kernels

        xbc, dt, a_log, d_skip, dt_bias = inputs
        f32 = jnp.float32
        t = self.seq_len
        b = xbc.shape[0] // t
        dims = (self.num_heads, self.head_dim, self.num_groups,
                self.state_size)
        xbc = xbc.reshape(b, t, -1)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        dt = dt.reshape(b, t, -1)
        pad = -t % self.chunk
        if pad:
            # dt = 0 past the end: decay 1, no input, outputs cut off below
            xbc, dt = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                       for v in (xbc, dt))
        kernel = pallas_kernels.ssd_chunk_applicable(dims, self.chunk,
                                                     xbc.dtype)
        _tel.inc("lower.scan_kernel.pallas_chunked" if kernel
                 else "lower.scan_kernel.xla_chunked")
        y = ssd_scan(xbc, dt, -jnp.exp(a_log.astype(f32)),
                     d_skip.astype(f32), dims, self.chunk, kernel)
        return [y[:, :t].reshape(b * t, -1)], []


# ---------------------------------------------------------------------------
# linear attention by the gated delta rule, on the same chunked skeleton
# (Yang, Kautz and Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464)
# ---------------------------------------------------------------------------
def gated_delta_chunked(q, k, v, g, beta, chunk):
    """One sequence of the gated delta rule ``S_t = a_t S_{t-1} + b_t k_t
    (v_t - a_t S_{t-1}^T k_t)^T``, ``o_t = S_t^T q_t`` (per head; S is
    ``[K, V]``, ``S_0 = 0``), by chunks of ``chunk`` positions. Where a
    diagonal decay (:func:`ssd_chunked`) needs a masked matrix product, the
    factors ``a_t (I - b_t k_t k_t^T)`` need the chunk's WY form: with
    ``u_t = b_t (v_t - a_t S_{t-1}^T k_t)`` the state is ``S_t = g_t S_0 +
    sum_{i<=t} (g_t/g_i) k_i u_i^T`` (``g_t`` the decay from the chunk's
    start), and the ``u`` of a chunk solve ONE unit-lower-triangular
    system, ``(I + A) U = B V - B G K S_0`` with ``A[t, i] = b_t (g_t/g_i)
    k_t.k_i`` below the diagonal. So

    1. all chunks at once: the system solved for its two right-hand sides
       (``U0 = (I+A)^-1 B V``, ``W = (I+A)^-1 B G K``; forward substitution,
       no power series: the powers of ``A`` overflow float32's mantissa long
       before they cancel), and from them each chunk's map of the state,
       ``S_end = M S_start + N``;
    2. one short ``lax.scan`` over the chunks carries the ``[K, V]`` state
       through those maps (a product a step; nothing else is sequential);
    3. all chunks at once: ``O = G Q S_start + ((Q K^T) * decay)(U0 - W
       S_start)``.

    Everything is float32 and every product is taken at ``HIGHEST``
    precision: the operations are few (12 MFLOP a chunk and head at 64 x
    96 x 192) and the state, the decays and the solve do not bear a
    rounding to bfloat16 (the gradient is autodiff's through these same
    float32 operations, so what cancels in it cancels as here).

    ``q``, ``k [T, H, K]`` (normalised, ``q`` scaled), ``v [T, H, V]``,
    ``g [T, H]`` (log of the decay, <= 0), ``beta [T, H]``; T whole chunks.
    Returns ``o [T, H, V]`` and the state at each chunk's start ``[T/chunk,
    H, K, V]``."""
    jax, jnp = _jax(), _jnp()
    from jax.scipy.linalg import solve_triangular

    hi = jax.lax.Precision.HIGHEST
    t, h, dk = k.shape
    dv = v.shape[-1]
    nc = t // chunk

    def cut(x):                         # [T, H, ...] -> [nc, H, L, ...]
        x = x.reshape((nc, chunk) + x.shape[1:])
        return jnp.moveaxis(x, 1, 2)

    q, k, v, g, beta = (cut(x) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                         # [nc, H, L]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    seg = gc[..., :, None] - gc[..., None, :]           # [.., t, i]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    kk = jnp.einsum("nhtd,nhid->nhti", k, k, precision=hi)
    a_mat = jnp.where(jnp.tril(lower, -1),
                      beta[..., None] * kk * decay, 0.0)
    rhs = jnp.concatenate([beta[..., None] * v,
                           (beta * jnp.exp(gc))[..., None] * k], axis=-1)
    # (I + A) U = rhs: the unit diagonal is implied, A's own is not read
    sol = solve_triangular(a_mat, rhs, lower=True, unit_diagonal=True)
    u0, w = sol[..., :dv], sol[..., dv:]
    k_end = k * jnp.exp(gc[..., -1:] - gc)[..., None]   # k_i g_L / g_i
    m = jnp.exp(gc[..., -1])[..., None, None] * jnp.eye(dk, dtype=k.dtype) \
        - jnp.einsum("nhld,nhle->nhde", k_end, w, precision=hi)
    n = jnp.einsum("nhld,nhle->nhde", k_end, u0, precision=hi)

    def carry(s, mn):
        return jnp.einsum("hde,hev->hdv", mn[0], s, precision=hi) + mn[1], s

    _, starts = jax.lax.scan(carry, jnp.zeros((h, dk, dv), k.dtype), (m, n))
    u = u0 - jnp.einsum("nhld,nhdv->nhlv", w, starts, precision=hi)
    qk = jnp.einsum("nhtd,nhid->nhti", q, k, precision=hi) * decay
    o = jnp.exp(gc)[..., None] * jnp.einsum("nhld,nhdv->nhlv", q, starts,
                                            precision=hi) \
        + jnp.einsum("nhti,nhiv->nhtv", qk, u, precision=hi)
    return jnp.moveaxis(o, 2, 1).reshape(t, h, dv), starts


# positions a sub-chunk of the per-channel body, here and in the kernels
SUB_CHUNK = DELTA_SUB_CHUNK


def gated_delta_chunked_channel(q, k, v, g, beta, chunk, sub=SUB_CHUNK):
    """:func:`gated_delta_chunked` with one decay a KEY CHANNEL (Kimi Delta
    Attention; Kimi Linear, arXiv:2510.26692): ``S_t = (I - b_t k_t k_t^T)
    Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T``, ``o_t = S_t^T q_t``, ``g [T,
    H, K]`` the log-decays (<= 0). The chunk's WY form stands as it is, with
    ``G_t = Diag(exp(c_t))`` (``c`` the decays summed from the chunk's start)
    where the scalar was: ``A[t, i] = b_t sum_d k_t[d] k_i[d] exp(c_t[d] -
    c_i[d])`` below the diagonal, ``(I + A) U = B V - B (K * exp(c)) S_0``,
    ``O = (Q * exp(c)) S_0 + P U`` with ``P[t, i] = sum_d q_t[d] k_i[d]
    exp(c_t[d] - c_i[d])`` on and below it, ``S_end = Diag(exp(c_L)) S_0 +
    (K * exp(c_L - c))^T U``.

    What changes is how ``A`` and ``P`` are formed: the decay no longer
    factors out of the inner product, and written ``(K * exp(c)) (K *
    exp(-c))^T`` the second factor overflows float32 (``exp(320)`` over 64
    positions at a log-decay of -5). So the chunk is cut into sub-chunks of
    ``sub`` positions and every exponent is referred to a sub-chunk's own
    MIDDLE: block row ``s`` is ``(X_s * exp(c_t - r_s)) (K * exp(r_s -
    c_i))^T`` (``r_s`` the sum up to the middle of sub-chunk ``s``), both
    factors within ``exp(+-sub/2 * |g|)`` inside ``s``'s own sub-chunk
    (``exp(+-40)`` at 16 x 5), the right one in (0, 1] before it, where
    an underflow loses what the true product has lost already; columns
    after sub-chunk ``s`` are above the diagonal and not formed. (Referred
    to the sub-chunk's START, as the published kernels' description has
    it, the left factor sinks to ``exp(-80)`` = 1.8e-35: inside float32,
    but the low-order parts of the six-pass ``HIGHEST`` product, 2^-16 of
    that, are subnormal and flushed, and the last positions of a sub-chunk
    came out at bfloat16's precision: 1e-3 against the recurrence at the
    bound, 3e-7 so.) Every other exponent (``c``, ``c_L - c``, ``c_L``) is
    <= 0.

    Float32 at ``HIGHEST`` and the solve by substitution, as the scalar
    body. ``q``, ``k [T, H, K]``, ``v [T, H, V]``, ``g [T, H, K]``, ``beta
    [T, H]``; T whole chunks of whole sub-chunks. Returns ``o [T, H, V]``
    and the chunk-start states ``[T/chunk, H, K, V]``."""
    jax, jnp = _jax(), _jnp()
    from jax.scipy.linalg import solve_triangular

    hi = jax.lax.Precision.HIGHEST
    t, h, dk = k.shape
    dv = v.shape[-1]
    nc, ns = t // chunk, chunk // sub

    def cut(x):                         # [T, H, ...] -> [nc, H, L, ...]
        x = x.reshape((nc, chunk) + x.shape[1:])
        return jnp.moveaxis(x, 1, 2)

    def subs(x):                        # [nc, H, L, K] -> [nc, H, ns, sub, K]
        return x.reshape(nc, h, ns, sub, dk)

    q, k, v, g, beta = (cut(x) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=2)                          # [nc, H, L, K]

    @jax.checkpoint
    def scores(q, k, gc, beta):
        """``A`` and ``P [nc, H, L, L]``. Recomputed in the backward pass:
        the scaled copies of q and k (the columns' FOUR times a key's
        bytes) are elementwise and cheap, and kept as residuals they were
        1.4 of the body's 3.0 GB at 8,192 x 32 x 128, with which the cell's
        step did not load (PR 40)."""
        # r_s: the decays summed up to the middle of sub-chunk s
        ref = gc[:, :, sub // 2 - 1::sub]               # [nc, H, ns, K]
        e_row = jnp.exp(subs(gc) - ref[:, :, :, None])
        # columns i against block row s: those of sub-chunks <= s
        seen = jnp.arange(chunk)[None, :] \
            < (jnp.arange(ns)[:, None] + 1) * sub
        seen = seen[None, None, :, :, None]             # [1, 1, ns, L, 1]
        e_col = jnp.where(seen, jnp.exp(jnp.where(
            seen, ref[:, :, :, None] - gc[:, :, None], 0.0)), 0.0)
        k_col = k[:, :, None] * e_col                   # [nc, H, ns, L, K]
        kk = jnp.einsum("nhstd,nhsid->nhsti", subs(k) * e_row, k_col,
                        precision=hi).reshape(nc, h, chunk, chunk)
        qk = jnp.einsum("nhstd,nhsid->nhsti", subs(q) * e_row, k_col,
                        precision=hi).reshape(nc, h, chunk, chunk)
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        return (jnp.where(jnp.tril(lower, -1), beta[..., None] * kk, 0.0),
                jnp.where(lower, qk, 0.0))

    a_mat, p_mat = scores(q, k, gc, beta)
    rhs = jnp.concatenate([beta[..., None] * v,
                           beta[..., None] * k * jnp.exp(gc)], axis=-1)
    sol = solve_triangular(a_mat, rhs, lower=True, unit_diagonal=True)
    u0, w = sol[..., :dv], sol[..., dv:]
    k_end = k * jnp.exp(gc[:, :, -1:] - gc)             # k_i G_L / G_i
    m = jnp.exp(gc[:, :, -1])[..., None] * jnp.eye(dk, dtype=k.dtype) \
        - jnp.einsum("nhld,nhle->nhde", k_end, w, precision=hi)
    n = jnp.einsum("nhld,nhle->nhde", k_end, u0, precision=hi)

    def carry(s, mn):
        return jnp.einsum("hde,hev->hdv", mn[0], s, precision=hi) + mn[1], s

    _, starts = jax.lax.scan(carry, jnp.zeros((h, dk, dv), k.dtype), (m, n))
    u = u0 - jnp.einsum("nhld,nhdv->nhlv", w, starts, precision=hi)
    o = jnp.einsum("nhld,nhdv->nhlv", q * jnp.exp(gc), starts, precision=hi) \
        + jnp.einsum("nhti,nhiv->nhtv", p_mat, u, precision=hi)
    return jnp.moveaxis(o, 2, 1).reshape(t, h, dv), starts


# float32 bytes of the per-channel body's intermediates that one run of it
# may hold: with all 32 heads of an 8,192-position sequence at 128 x 128 at
# once the body's forward and backward held 3.0 GB, and the cell's step was
# 0.2 GB over the chip (PR 40); 8 heads a run hold 0.8
_CHANNEL_RUN_BYTES = 1 << 30


def _channel_scan(seq_ops, head_ops, chunk, prepare, out_dtype):
    """:func:`gated_delta_chunked_channel` over sequences, a GROUP OF HEADS
    at a time where all of them together would hold more than
    ``_CHANNEL_RUN_BYTES`` of float32 intermediates (reckoned from the
    shapes: a position and head holds about ``2 ns K`` for the columns'
    scaled keys, ``10 K + 4 V`` for the other scaled copies, right-hand
    sides and solutions, ``4 L`` for the chunk's square matrices).
    ``seq_ops`` are ``[B, T, H, ...]``, ``head_ops`` ``[H, ...]``;
    ``prepare(*seq_ops, *head_ops)`` of one sequence's group (``[T, hg,
    ...]``, ``[hg, ...]``) gives the body's float32 ``q, k, v, g, beta``,
    so that what is float32 exists a group at a time too; the result is
    cut to ``T`` and cast to ``out_dtype`` inside. The heads are
    independent, so the result is the same; each group runs under
    ``jax.checkpoint``, so that the backward pass holds one group's
    intermediates and not all groups' residuals (at the price of the
    group's forward pass once more). One group: the plain body."""
    jax, jnp = _jax(), _jnp()

    b, t, h = seq_ops[0].shape[:3]
    dk, dv = seq_ops[1].shape[-1], seq_ops[2].shape[-1]
    pad = -t % chunk
    a_head = 4 * (t + pad) * (2 * (chunk // SUB_CHUNK) * dk + 10 * dk
                              + 4 * dv + 4 * chunk)
    hg = max(d for d in range(1, h + 1)
             if h % d == 0 and (d == 1 or d * a_head <= _CHANNEL_RUN_BYTES))

    def one(x):
        ops = prepare(*x[0], *x[1])
        if pad:
            # past the end: decay 1, beta 0, no key: the state stands still
            ops = [jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                   for v in ops]
        return gated_delta_chunked_channel(*ops, chunk=chunk)[0][:t].astype(
            out_dtype)

    if hg == h:
        return jax.lax.map(lambda s: one((s, head_ops)), tuple(seq_ops))

    def groups(x):      # [B, T, H, ...] -> [B * H/hg, T, hg, ...]
        x = x.reshape((b, t, h // hg, hg) + x.shape[3:])
        return jnp.moveaxis(x, 2, 1).reshape((-1, t, hg) + x.shape[4:])

    def head_groups(x):     # [H, ...] -> [B * H/hg, hg, ...]
        x = x.reshape((1, h // hg, hg) + x.shape[1:])
        return jnp.broadcast_to(x, (b,) + x.shape[1:]).reshape(
            (-1, hg) + x.shape[3:])

    o = jax.lax.map(jax.checkpoint(one),
                    (tuple(groups(x) for x in seq_ops),
                     tuple(head_groups(x) for x in head_ops)))
    return jnp.moveaxis(o.reshape(b, h // hg, t, hg, dv), 1, 2).reshape(
        b, t, h, dv)


def gated_delta_scan(q, k, v, g, beta, chunk, kernel):
    """The gated delta rule over sequences of whole chunks as ONE
    differentiable function, beside :func:`ssd_scan`: ``q``, ``k [B, T, H,
    K]``, ``v [B, T, H, V]``, ``beta [B, T, H]`` and ``g [B, T, H]``, one
    decay a head, or ``[B, T, H, K]``, one a key channel; float32; returns
    ``o [B, T, H, V]``, float32: the caller rounds it. ``kernel`` picks the
    body: the Pallas chunk kernels' HEAD-MAJOR entry (``pallas_kernels.
    delta_chunk_forward`` / ``_backward``: the solve, the chunk's matrices
    and the carried state stay in VMEM; they read float32 ``[B, H, T, .]``
    transposes of these arguments; :func:`gated_delta_rows` is the entry
    that reads the op's own rows) with the backward pass written out, its
    residuals the five inputs and the float32 chunk-start states ``[B,
    T/chunk, H, K, V]``; or the same chunked
    algorithm in ``jax.numpy`` under autodiff, :func:`gated_delta_chunked`
    a sequence at a time or, a decay a channel,
    :func:`gated_delta_chunked_channel` a group of heads at a time
    (:func:`_channel_scan`)."""
    jax = _jax()
    from . import pallas_kernels

    if not kernel and g.ndim == 4:
        return _channel_scan((q, k, v, g, beta), (), chunk,
                             lambda *ops: ops, q.dtype)
    if not kernel:
        # one decay a head
        return jax.lax.map(
            lambda x: gated_delta_chunked(*x, chunk=chunk)[0],
            (q, k, v, g, beta))

    return _chunk_kernels(pallas_kernels.delta_chunk_forward,
                          pallas_kernels.delta_chunk_backward,
                          chunk=chunk)(q, k, v, g, beta)


def _chunk_kernels(forward, backward, **static):
    """One differentiable function over a forward and a backward chunk
    kernel: the value is ``forward(*args, with_states=False)``'s; under
    differentiation the forward kernel also writes the float32 chunk-start
    states, and ``backward(*args, states, do)`` reads them back beside the
    arguments, which are the residuals."""
    jax = _jax()

    @jax.custom_vjp
    def f(*args):
        return forward(*args, with_states=False, **static)[0]

    def f_fwd(*args):
        o, starts = forward(*args, with_states=True, **static)
        return o, args + (starts,)

    def f_bwd(res, do):
        return backward(*res, do, **static)

    f.defvjp(f_fwd, f_bwd)
    return f


def gated_delta_rows(query, key, value, gate, beta, scale, bias, spec):
    """:func:`gated_delta_scan`'s kernel body over the op's arrays AS THE
    PROJECTIONS LEAVE THEM, for shapes ``pallas_kernels.
    delta_rows_applicable`` admits (``spec``: a ``pallas_kernels.
    DeltaRows``): ``query``, ``key [rows, Hk * K]`` and ``value [rows, H *
    V]`` in the compute dtype, NOT normalised; ``beta [B, T, H]`` float32;
    ``gate`` the log-decay a head ``[B, T, H]`` float32 (``scale``, ``bias``
    ``None``) or, a decay a key channel, the op's ``a [rows, H * K]`` in the
    compute dtype with the float32 rows ``scale = exp(A_log)`` a channel and
    ``bias = dt_bias``, ``[1, H * K]``. The casts, the two normalisations, a
    channel's gate and the shared key heads happen in VMEM
    (``pallas_kernels.delta_rows_forward`` / ``_backward``), so no float32
    or head-major copy of a wide array exists on either side of the calls.
    Returns ``o [rows, H * V]`` in the compute dtype. The residuals are the
    inputs as they came and the float32 chunk-start states ``[B, T/chunk,
    H, K, V]``; the gradients of ``scale`` and ``bias`` are float32 sums
    over every row, never rounded."""
    from . import pallas_kernels

    return _chunk_kernels(
        pallas_kernels.delta_rows_forward, pallas_kernels.delta_rows_backward,
        spec=spec)(query, key, value, gate, beta, scale, bias)


@register_op("GatedDeltaRule")
class GatedDeltaRule(Operator):
    """Linear attention by the gated delta rule over whole sequences,
    chunked (:func:`gated_delta_chunked`), beside ``SSMScan``. Per position
    and head, from the mixer's convolved and activated projections
    ``query``, ``key`` ``[rows, Hk*K]``, ``value`` ``[rows, H*V]``, the step
    gate ``b`` ``[rows, H]`` and the decay gate ``a``, both before their
    nonlinearities:

    ``q = query / |query|_2 / sqrt(K)``, ``k = key / |key|_2``;
    ``beta = sigmoid(b)``, doubled under ``neg_eigval`` (the factor ``I -
    beta k k^T`` may then reflect: eigenvalues in (-1, 1));
    ``alpha = exp(g)``, ``g`` the log-decay (below);
    ``S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T``,
    ``o_t = S_t^T q_t``, ``S_0 = 0`` at each sequence's start.

    **Key heads.** ``num_key_heads`` ``Hk`` (0, the default: ``H``) query/key
    heads under ``H = num_heads`` value heads: value head ``j`` reads key head
    ``j // (H / Hk)`` with its OWN decay and step gate, so the recurrence
    runs ``H`` states of ``K x V`` (Qwen3-Next: 16 under 32). ``q`` and ``k``
    are normalised once a key head and then REPEATED to the value heads
    (``jnp.repeat`` on the float32 ``[B, T, Hk, K]``; the bodies and the
    head-major kernels see ``H`` heads as ever, and autodiff sums each
    pair's ``dq``, ``dk``); with ``Hk = H`` nothing is repeated. On the
    kernels' ROW-MAJOR entry (below) no copy is repeated at all: value head
    ``i`` of a grid step reads key head ``i // ratio`` of the step's block,
    and a key head's ``dq``, ``dk`` are summed over its value heads in
    float32 in VMEM. Counted ``lower.delta_rule_heads.grouped`` / ``.equal``
    a traced node.

    **What the op covers.** The decay's SHAPE is read off ``a``: ``[rows,
    H]`` is one decay a head (Gated DeltaNet, arXiv:2412.06464; ``dt_bias
    [H]``), ``[rows, H*K]`` one a KEY CHANNEL (Kimi Delta Attention,
    arXiv:2510.26692; ``dt_bias [H*K]``; ``alpha_t`` is then
    ``Diag(exp(g_t))`` on the state's key axis: ``S_t = (I - beta_t k_t
    k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``). ``A_log`` is ``[H]``
    in both. The gate's FORM is ``gate_floor``: 0 (the default) is ``g =
    -exp(A_log) softplus(a + dt_bias)``, unbounded below; a negative floor
    is the bounded gate ``g = gate_floor * sigmoid(exp(A_log) (a +
    dt_bias))``, every log-decay in ``(gate_floor, 0)``, which is what
    keeps the per-channel chunk's intermediates inside float32
    (:func:`gated_delta_chunked_channel`: -5 x a 16-position sub-chunk =
    -80). Either form goes with either shape; a per-channel gate whose
    channels are equal computes the per-head op. Counted
    ``lower.delta_rule_gate.head`` / ``.channel`` a traced node.

    The normalisation, the doubling and the decay are inside the op, in
    float32 like the state and the solve, whatever the compute dtype; the
    result is rounded to it once, and so is each wide gradient (``query``,
    ``key``, ``value``, a channel's ``a``), after the float32 backward
    passes of the normalisation and the gate. ``A_log``'s and ``dt_bias``'s
    gradients are float32 sums over every row, never rounded.

    The recurrence's body (:func:`gated_delta_scan`) is chosen from the
    shapes when the node is traced, as ``SSMScan`` and ``CausalAttention``
    choose: one Pallas chunk kernel a pass where the chunk is whole sublane
    tiles and a head's widths are whole sublanes up to 128 keys and 256
    values (``lower.delta_rule_kernel.pallas_chunked``; 64 x 96 x 192 at 15
    heads is such a shape), else the same chunked algorithm in
    ``jax.numpy`` under autodiff (``lower.delta_rule_kernel.xla_chunked``).
    A decay a channel takes the same kernels where besides the keys are
    whole lane tiles (128 a head) and the chunk whole 16-position
    sub-chunks, at most four (``pallas_kernels.delta_channel_applicable``;
    64 x 128 x 128 at 32 heads is such a shape), the chunk's ``A`` and
    ``P`` formed a block row a sub-chunk as the ``jax.numpy`` body forms
    them and the decay's gradient ``[rows, H*K]`` wide; else that body
    (:func:`gated_delta_chunked_channel`), a group of heads at a time.
    In both, every operand and product is float32 (products at ``HIGHEST``:
    six bfloat16 passes, never one), as are the decays, the solve, the
    carried state, its gradient and every accumulator. The kernels' backward
    pass is written out: it keeps its inputs and the float32 chunk-start
    states ``[B, T/chunk, H, K, V]`` and forms each chunk's matrices again
    in VMEM, so under segment recomputation the op runs forward, forward,
    backward.

    **Where the kernels read the rows** (``lower.delta_rule_layout.rows`` /
    ``.heads`` a traced node that takes the kernels; chosen from the shapes
    alone, ``pallas_kernels.delta_rows_applicable``). At heads of whole lane
    tiles (128 keys, 128 or 256 values) over sequences of whole chunks, a
    step's value heads covering whole key heads (32 heads of 128 x 128 a
    decay a channel, and 32 over 16 a decay a head, are such shapes), the
    kernels take THIS OP'S INPUTS as the projections leave them
    (:func:`gated_delta_rows`): ``query``, ``key``, ``value`` and a
    channel's ``a`` row-major in the compute dtype, blocks of a step's
    heads' lanes; the casts, the two normalisations, a channel's gate and
    the shared key heads happen in VMEM, the result and the wide gradients
    are rounded at the kernels' stores, and the residuals are the inputs as
    they came (which the segment holds anyway) and the chunk-start states.
    Only what is a head wide is formed here in XLA: ``beta``, a head's
    decay, ``exp(A_log)`` a channel. Everywhere else (96 x 192; a sequence
    of part chunks) the op casts, normalises, gates, repeats and transposes
    in XLA and the kernels read float32 head-major copies
    (:func:`gated_delta_scan`), whose five are then the residuals."""

    name_hint = "gateddeltarule"
    PARAMS = {
        "num_heads": Param(int, REQUIRED),
        "num_key_heads": Param(int, 0, "query/key heads, each read by "
                               "num_heads / num_key_heads value heads; 0: "
                               "num_heads"),
        "key_dim": Param(int, REQUIRED, "a head's query/key width"),
        "value_dim": Param(int, REQUIRED, "a head's value width"),
        "chunk": Param(int, 64),
        "seq_len": Param(int, REQUIRED),
        "neg_eigval": Param(bool, False, "beta in (0, 2), not (0, 1)"),
        "gate_floor": Param(float, 0.0, "0: log-decay -exp(A_log) softplus(a "
                            "+ dt_bias); negative: gate_floor * sigmoid("
                            "exp(A_log) (a + dt_bias)), bounded below"),
    }
    # the decay compounds over a sequence: its parameters stay float32
    full_precision_args = ("A_log", "dt_bias")
    NORM_EPS = 1e-6     # under the root of |x|^2, as the published kernels

    def list_arguments(self):
        return ["query", "key", "value", "a", "b", "A_log", "dt_bias"]

    def infer_shape(self, in_shapes):
        q, a = in_shapes[0], in_shapes[3]
        if q is None:
            raise MXNetError("GatedDeltaRule: query shape unknown")
        h, hk = self.num_heads, self.num_key_heads or self.num_heads
        if hk < 1 or h % hk:
            raise MXNetError("GatedDeltaRule: %d value heads do not share %d "
                             "key heads evenly" % (h, hk))
        if q[1] != hk * self.key_dim:
            raise MXNetError("GatedDeltaRule: query width %d is not %d heads "
                             "of %d" % (q[1], hk, self.key_dim))
        if self.gate_floor > 0:
            raise MXNetError("GatedDeltaRule: gate_floor %g is above 0"
                             % self.gate_floor)
        _sequences(q[0], self.seq_len, "GatedDeltaRule")
        rows = q[0]
        # a decay a head unless ``a`` comes a key channel wide
        gate = h if a is None else a[1]
        if gate not in (h, h * self.key_dim):
            raise MXNetError("GatedDeltaRule: a of width %d is neither a "
                             "decay a head (%d) nor a key channel (%d)"
                             % (gate, h, h * self.key_dim))
        return ([q, q, (rows, h * self.value_dim), (rows, gate), (rows, h),
                 (h,), (gate,)], [(rows, h * self.value_dim)], [])

    def remat_results(self, in_shapes, in_types):
        """Kept always under recomputation: the result, one activation of
        the values' width."""
        rows = in_shapes[0][0]
        return [("output", rows * self.num_heads * self.value_dim
                 * np.dtype(in_types[0]).itemsize, None)]

    def apply(self, ctx, inputs, aux):
        jax, jnp = _jax(), _jnp()
        from .. import telemetry as _tel
        from . import pallas_kernels

        f32 = jnp.float32
        t, h = self.seq_len, self.num_heads
        n = inputs[0].shape[0] // t
        ratio = h // (self.num_key_heads or h)
        _tel.inc("lower.delta_rule_heads.%s"
                 % ("grouped" if ratio > 1 else "equal"))

        def heads(x):
            return x.reshape(n, t, h, -1)

        def shared(x):
            """``[B, T, Hk, K]`` -> ``[B, T, H, K]``: a key head once a
            value head that reads it."""
            return x if ratio == 1 else jnp.repeat(x, ratio, axis=2)

        def key_heads(x):
            return x.reshape(n, t, h // ratio, -1)

        def unit(x):
            return x * jax.lax.rsqrt(
                jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                + self.NORM_EPS)

        def gate(a, a_log, dt_bias):
            if self.gate_floor:
                return self.gate_floor * jax.nn.sigmoid(jnp.exp(a_log)
                                                        * (a + dt_bias))
            return -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)

        def step_gate(b):
            beta = jax.nn.sigmoid(b).reshape(n, t, h)
            return 2.0 * beta if self.neg_eigval else beta

        channel = inputs[3].shape[1] != h
        _tel.inc("lower.delta_rule_gate.%s"
                 % ("channel" if channel else "head"))
        dims = (h, self.key_dim, self.value_dim)
        if channel:
            if self.chunk % SUB_CHUNK:
                raise MXNetError("GatedDeltaRule: a decay a channel wants "
                                 "chunks of whole %d-position sub-chunks, "
                                 "not %d" % (SUB_CHUNK, self.chunk))
            kernel = pallas_kernels.delta_channel_applicable(
                dims, self.chunk, jnp.dtype(f32))
        else:
            kernel = pallas_kernels.delta_chunk_applicable(
                dims, self.chunk, jnp.dtype(f32))
        _tel.inc("lower.delta_rule_kernel.pallas_chunked" if kernel
                 else "lower.delta_rule_kernel.xla_chunked")
        rows = kernel and pallas_kernels.delta_rows_applicable(
            dims, h // ratio, self.chunk, t, channel)
        if kernel:
            _tel.inc("lower.delta_rule_layout.%s"
                     % ("rows" if rows else "heads"))
        if rows:
            # the kernels read the wide arrays where they lie, as they come;
            # what is a head wide is formed here, in float32
            b, a_log, dt_bias = (x.astype(f32) for x in inputs[4:])
            if channel:
                g, gate_rows = inputs[3], (
                    jnp.repeat(jnp.exp(a_log), self.key_dim)[None],
                    dt_bias[None])
            else:
                g, gate_rows = gate(inputs[3].astype(f32), a_log,
                                    dt_bias).reshape(n, t, h), (None, None)
            o = gated_delta_rows(
                *inputs[:3], g, step_gate(b), *gate_rows,
                pallas_kernels.DeltaRows(
                    t, h, h // ratio, self.key_dim, self.value_dim,
                    self.chunk, self.gate_floor, self.NORM_EPS))
            return [ctx.keep(o, "output")], []
        q, k, v, a, b, a_log, dt_bias = (x.astype(f32) for x in inputs)
        if channel and not kernel:
            def prepare(q, k, v, a, b, a_log, dt_bias):
                """One sequence's group of heads, as it comes (``[T, hg,
                .]`` in the compute dtype; ``a_log [hg]``, ``dt_bias [hg,
                K]``) -> the body's float32 operands."""
                q, k, v, a, b = (x.astype(f32) for x in (q, k, v, a, b))
                beta = jax.nn.sigmoid(b)
                return (unit(q) * (self.key_dim ** -0.5), unit(k), v,
                        gate(a, a_log[:, None], dt_bias),
                        2.0 * beta if self.neg_eigval else beta)

            raw = inputs[:5]
            o = _channel_scan(
                [shared(key_heads(x)) for x in raw[:2]]
                + [heads(x) for x in raw[2:4]] + [raw[4].reshape(n, t, h)],
                (a_log, dt_bias.reshape(h, self.key_dim)), self.chunk,
                prepare, raw[0].dtype)
            return [ctx.keep(o.reshape(n * t, -1), "output")], []
        q = shared(unit(key_heads(q)) * (self.key_dim ** -0.5))
        k, v = shared(unit(key_heads(k))), heads(v)
        if channel:
            g = gate(heads(a), a_log[:, None],
                     dt_bias.reshape(h, self.key_dim))
        else:
            g = gate(a, a_log, dt_bias).reshape(n, t, h)
        beta = step_gate(b)
        pad = -t % self.chunk
        if pad:
            # past the end: decay 1, beta 0, no key: the state stands still
            q, k, v, g, beta = (
                jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                for x in (q, k, v, g, beta))
        o = gated_delta_scan(q, k, v, g, beta, self.chunk, kernel)
        o = o[:, :t].reshape(n * t, -1).astype(inputs[0].dtype)
        return [ctx.keep(o, "output")], []
