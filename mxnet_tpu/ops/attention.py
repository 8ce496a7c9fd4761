"""Causal grouped-head attention with rotary positions.

Beyond the reference (2016 MXNet has no attention operator); the op the
``*`` layers of a hybrid language model lower to. Layout as the other
language-model ops (``ops/seq.py``): ``[rows, width]`` activations whose
rows are whole sequences of ``seq_len`` positions.

Which implementation a program takes is decided from the shapes when it
is traced, and counted (``lower.attention_kernel.<name>``):

* ``pallas_splash``: JAX's own Pallas TPU kernel (``jax.experimental.
  pallas.ops.tpu.splash_attention``) in its multi-query form, where it
  applies: a head of whole 128-lanes and a sequence of whole
  ``SPLASH_BLOCK`` blocks. One call a key/value head: its ``G`` query
  heads read that one head's keys, so nothing is repeated; forward and
  backward are blockwise in the kernel (scores and softmax float32 in
  VMEM, products in the compute dtype), blocks above the diagonal are
  skipped, and no ``[T, T]`` tensor reaches HBM in either pass. The XLA
  lowering below wrote its float32 scores out and read them back several
  times a pass: 577 of a 1,104 ms step at 8,192 tokens on the v5e
  (PERF.md, PR 26).
* ``xla_blockwise``: everything else. Queries go in blocks of
  ``BLOCK_Q``; a block reads only the keys at or before its end, so the
  upper triangle is never computed, and each block runs under
  ``jax.checkpoint``: the backward pass recomputes one block's scores at
  a time and no ``[T, T]`` tensor exists in either pass. Scores, softmax
  and accumulation are float32, the two products take inputs in the
  compute dtype. The ``G`` query heads that share a key/value head ride
  one einsum against it.

The repo's own :func:`pallas_kernels.flash_attention` is not among them:
it has no grouped form (repeating keys is what this op exists to avoid)
and its backward pass recomputes the full ``[T, T]`` scores, so at the
shapes a language model brings it cannot apply (ROADMAP Reach A1).
"""
from __future__ import annotations

import functools

import numpy as np

from ..base import MXNetError
from .registry import Operator, Param, REQUIRED, register_op
from .seq import _sequences

BLOCK_Q = 512
SPLASH_BLOCK = 512
_NEG = -1e30


def rope(x, theta, scale=1.0, rotary_dim=0):
    """Rotary position embedding (Su et al., arXiv:2104.09864), the
    half-split convention of the published modelling code: ``x [T, ...,
    D]``, position = index along axis 0, over the whole head or, with
    ``rotary_dim``, over the LAST ``rotary_dim`` columns of it (the
    frequencies are those of a head ``rotary_dim`` wide; the columns before
    pass through). The result is multiplied by ``scale`` before it is
    rounded to ``x``'s dtype."""
    import jax.numpy as jnp

    t, d = x.shape[0], x.shape[-1]
    keep = d - rotary_dim if rotary_dim else 0
    half = (d - keep) // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None]
    shape = (t,) + (1,) * (x.ndim - 2) + (half,)
    cos = jnp.asarray(np.cos(ang), jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(ang), jnp.float32).reshape(shape)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., keep:keep + half], xf[..., keep + half:]
    out = jnp.concatenate(([xf[..., :keep]] if keep else [])
                          + [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return (out * scale if scale != 1.0 else out).astype(x.dtype)


@functools.lru_cache(None)
def _splash_kernel(t, group, block, interpret, keep_name):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks)

    import jax

    mask = masks.MultiHeadMask([masks.CausalMask((t, t))
                                for _ in range(group)])
    sizes = kernel.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    # the kernel's block tables are arrays made here: outside any trace,
    # or a cached kernel would carry one program's tracers into the next
    with jax.ensure_compile_time_eval():
        return kernel.make_splash_mqa_single_device(
            mask, block_sizes=sizes, interpret=interpret,
            residual_checkpoint_name=keep_name)


def attend_splash(q, k, v, keep_name=None):
    """``q [B, Hkv, G, T, D]`` (already scaled by 1/sqrt(D)), ``k, v [B,
    Hkv, T, D]`` -> ``[B, Hkv, G, T, D]``: one multi-query kernel call a
    (sequence, key/value head). The interpreter on ``cpu``, the Mosaic
    kernel elsewhere (``pallas_kernels.pallas_call``'s rule). Under
    ``keep_name`` the kernel marks its output and log-sum-exp, the
    backward kernels' residuals, for a recomputation to keep."""
    import jax

    t, group = q.shape[3], q.shape[2]
    block = min(SPLASH_BLOCK, t)

    def run(interpret):
        one = _splash_kernel(t, group, block, interpret, keep_name)
        return lambda q, k, v: jax.vmap(jax.vmap(one))(q, k, v)

    return jax.lax.platform_dependent(q, k, v, cpu=run(True),
                                      default=run(False))


def _block(q, k, v, start, scale):
    """Queries ``q [nq, Hkv, G, D]`` at positions ``start..`` against the
    keys ``k, v [nk, Hkv, D]`` at positions ``0..nk``."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("qhgd,khd->hgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = start + jnp.arange(q.shape[0])
    mask = jnp.arange(k.shape[0])[None, :] <= qpos[:, None]
    p = jax.nn.softmax(jnp.where(mask, s, _NEG), axis=-1)
    return jnp.einsum("hgqk,khd->qhgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def attend_blockwise(q, k, v, scale, block=BLOCK_Q):
    """One sequence: ``q [T, Hkv, G, D]``, ``k, v [T, Hkv, D]``."""
    import jax
    import jax.numpy as jnp

    t = q.shape[0]
    outs = []
    for i in range(0, t, block):
        n = min(i + block, t)
        fn = jax.checkpoint(functools.partial(_block, start=i, scale=scale))
        outs.append(fn(q[i:n], k[:n], v[:n]))
    return jnp.concatenate(outs, axis=0)


@register_op("CausalAttention")
class CausalAttention(Operator):
    """``softmax(q k^T / sqrt(D) + causal mask) v`` per head, ``num_heads``
    query heads sharing ``num_kv_heads`` key/value heads (Ainslie et al.,
    GQA, arXiv:2305.13245) without repeating keys. ``rotary`` applies
    rotary positions to queries and keys first: over the whole head, or
    over its last ``rotary_dim`` columns (a head that is part content,
    part position: latent attention's 192 + 64)."""

    name_hint = "causalattention"
    PARAMS = {
        "num_heads": Param(int, REQUIRED),
        "num_kv_heads": Param(int, REQUIRED),
        "head_dim": Param(int, REQUIRED),
        "seq_len": Param(int, REQUIRED),
        "rotary": Param(bool, True),
        "rope_theta": Param(float, 10000.0),
        "rotary_dim": Param(int, 0, "rotate the last rotary_dim columns of "
                            "each head only; 0: the whole head"),
    }

    def list_arguments(self):
        return ["query", "key", "value"]

    def infer_shape(self, in_shapes):
        q = in_shapes[0]
        if q is None:
            raise MXNetError("CausalAttention: query shape unknown")
        if self.num_heads % self.num_kv_heads:
            raise MXNetError("CausalAttention: %d query heads do not share "
                             "%d key/value heads evenly"
                             % (self.num_heads, self.num_kv_heads))
        if self.rotary_dim % 2 or not 0 <= self.rotary_dim <= self.head_dim:
            raise MXNetError("CausalAttention: rotary_dim %d of a head of %d"
                             % (self.rotary_dim, self.head_dim))
        if q[1] != self.num_heads * self.head_dim:
            raise MXNetError("CausalAttention: query width %d is not %d "
                             "heads of %d" % (q[1], self.num_heads,
                                              self.head_dim))
        _sequences(q[0], self.seq_len, "CausalAttention")
        kv = (q[0], self.num_kv_heads * self.head_dim)
        return [q, kv, kv], [q], []

    def _splash_applies(self):
        t = self.seq_len
        return (self.head_dim % 128 == 0 and t % 128 == 0
                and t % min(SPLASH_BLOCK, t) == 0)

    def remat_results(self, in_shapes, in_types):
        """Kept always under recomputation: the kernel's output and, on
        the splash path, its float32 log-sum-exp a (head, position), the
        backward kernels' residuals: a step runs the forward kernel once.
        Projections and rotary are recomputed."""
        rows, width = in_shapes[0]
        return [("attention", rows * width * np.dtype(in_types[0]).itemsize
                 + 4 * rows * self.num_heads, None)]

    def apply(self, ctx, inputs, aux):
        import jax

        from .. import telemetry as _tel
        from . import pallas_kernels

        q, k, v = inputs
        t, hq, hkv, d = (self.seq_len, self.num_heads, self.num_kv_heads,
                         self.head_dim)
        b = q.shape[0] // t
        scale = 1.0 / float(np.sqrt(d))
        q = q.reshape(b, t, hq, d)
        k = k.reshape(b, t, hkv, d)
        v = v.reshape(b, t, hkv, d)
        splash = self._splash_applies() and pallas_kernels.pallas_available()
        if self.rotary:
            # the splash kernel takes queries already scaled: folded into
            # the rotation, before the one rounding to the compute dtype
            q = jax.vmap(functools.partial(
                rope, theta=self.rope_theta,
                scale=scale if splash else 1.0,
                rotary_dim=self.rotary_dim))(q)
            k = jax.vmap(functools.partial(rope, theta=self.rope_theta,
                                           rotary_dim=self.rotary_dim))(k)
        elif splash:
            q = (q.astype("float32") * scale).astype(q.dtype)
        if splash:
            _tel.inc("lower.attention_kernel.pallas_splash")
            qg = q.reshape(b, t, hkv, hq // hkv, d).transpose(0, 2, 3, 1, 4)
            out = attend_splash(qg, k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3),
                                ctx.kept.get("attention"))
            out = out.transpose(0, 3, 1, 2, 4)
            return [out.reshape(b * t, hq * d).astype(inputs[0].dtype)], []
        _tel.inc("lower.attention_kernel.xla_blockwise")
        qg = q.reshape(b, t, hkv, hq // hkv, d)
        out = jax.lax.map(
            lambda x: attend_blockwise(x[0], x[1], x[2], scale),
            (qg, k, v))
        return [ctx.keep(out.reshape(b * t, hq * d), "attention")], []
