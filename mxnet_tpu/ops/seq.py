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


def ssd_chunked(x, dt, a_head, b_mat, c_mat, chunk):
    """One sequence of the selective state-space recurrence
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t`` (per
    head; S is ``[P, N]``), computed by chunks of ``chunk`` positions:
    inside a chunk the quadratic form (a masked ``[chunk, chunk]`` decay
    matrix times ``C B^T``), between chunks the carried state. Decays,
    cumulative sums and the carried state are float32; the matrix
    products take their inputs in ``x.dtype`` and accumulate in float32.

    ``x [T, H, P]``, ``dt [T, H]`` (after softplus, float32), ``a_head
    [H]`` (negative, float32), ``b_mat``/``c_mat [T, G, N]``; head h reads
    group ``h // (H/G)``. Returns ``y [T, H, P]`` float32. Nothing here is
    ``[T, T]`` and no per-position state exists, so what autodiff keeps is
    linear in T; callers wrap it in ``jax.checkpoint`` so that only the
    inputs outlive the forward pass."""
    jax, jnp = _jax(), _jnp()
    f32 = jnp.float32
    t_real, h, p = x.shape
    g, n = b_mat.shape[1:]
    hg = h // g
    cd = x.dtype
    pad = -t_real % chunk
    if pad:
        # dt = 0 past the end: decay 1, no input, outputs cut off below
        x, dt, b_mat, c_mat = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                               for v in (x, dt, b_mat, c_mat))
    nc = x.shape[0] // chunk
    xs = x.reshape(nc, chunk, g, hg, p)
    bs = b_mat.reshape(nc, chunk, g, n)
    cs_ = c_mat.reshape(nc, chunk, g, n)
    dts = dt.reshape(nc, chunk, g, hg)
    cum = jnp.cumsum(dts * a_head.reshape(g, hg), axis=1)   # [nc, L, g, hg]
    # inside the chunk: y_t += sum_{s<=t} exp(cum_t - cum_s) (C_t.B_s) dt_s x_s
    cb = jnp.einsum("ctgn,csgn->cgts", cs_, bs, preferred_element_type=f32)
    cum_h = cum.transpose(0, 2, 3, 1)                       # [nc, g, hg, L]
    seg = cum_h[..., :, None] - cum_h[..., None, :]         # [.., t, s]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    m = cb[:, :, None] * decay * dts.transpose(0, 2, 3, 1)[..., None, :]
    y = jnp.einsum("cghts,csghp->ctghp", m.astype(cd), xs,
                   preferred_element_type=f32)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, -1:] - cum) * dts               # [nc, L, g, hg]
    xw = (xs.astype(f32) * to_end[..., None]).astype(cd)
    states = jnp.einsum("csghp,csgn->cghpn", xw, bs,
                        preferred_element_type=f32)
    # between chunks: the carried state, float32, one step a chunk

    def carry(s, inp):
        d, st = inp
        return d[..., None, None] * s + st, s               # emits the START

    _, starts = jax.lax.scan(carry, jnp.zeros((g, hg, p, n), f32),
                             (jnp.exp(cum[:, -1]), states))
    y = y + jnp.einsum("ctgn,cghpn->ctghp", cs_, starts.astype(cd),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    return y.reshape(nc * chunk, h, p)[:t_real]


@register_op("SSMScan")
class SSMScan(Operator):
    """The selective state-space scan of a Mamba-2 mixer over whole
    sequences, chunked (:func:`ssd_chunked`), beside the LSTM's fused scan
    above. ``data`` is ``[rows, H*P + 2*G*N]``: per position ``x`` (H heads
    of P), ``B`` and ``C`` (G groups of N), as the mixer's convolution
    leaves them; ``dt`` is ``[rows, H]`` before its bias and softplus.
    ``y = scan(x, softplus(dt + dt_bias), -exp(A_log), B, C) + D * x``.
    The backward pass is autodiff of the chunked form under
    ``jax.checkpoint``, one sequence at a time: memory linear in
    ``seq_len``, inputs the only residuals."""

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

        _tel.inc("lower.scan_kernel.xla_chunked")
        xbc, dt, a_log, d_skip, dt_bias = inputs
        f32 = jnp.float32
        di, gn = self._widths()
        t = self.seq_len
        b = xbc.shape[0] // t
        h, p, g, n = (self.num_heads, self.head_dim, self.num_groups,
                      self.state_size)
        x = xbc[:, :di].reshape(b, t, h, p)
        b_mat = xbc[:, di:di + gn].reshape(b, t, g, n)
        c_mat = xbc[:, di + gn:].reshape(b, t, g, n)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        a_head = -jnp.exp(a_log.astype(f32))
        chunk = self.chunk

        @jax.checkpoint
        def one(x, dt, b_mat, c_mat, a_head):
            return ssd_chunked(x, dt, a_head, b_mat, c_mat, chunk)

        y = jax.lax.map(lambda v: one(*v, a_head),
                        (x, dt.reshape(b, t, h), b_mat, c_mat))
        y = y + d_skip.astype(f32)[:, None] * x.astype(f32)
        return [y.reshape(b * t, di).astype(xbc.dtype)], []
