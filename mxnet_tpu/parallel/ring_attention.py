"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Long-context scaling primitives (beyond the reference, which predates
attention — SURVEY §5 long-context: the reference's story was bucketing +
scan; these primitives are what a modern user of the framework needs for
long sequences):

* :func:`ring_attention` — Q/K/V sharded along the sequence axis of a
  mesh; K/V blocks rotate around the ring via ``lax.ppermute`` (ICI
  neighbor exchange) while each device accumulates its queries' attention
  with a numerically-stable online softmax (flash-attention style
  running max / normalizer). Memory per device is O(T/n), enabling
  contexts n× longer than one chip's HBM.
* :func:`ulysses_attention` — all-to-all sequence parallelism: heads are
  exchanged for sequence via ``lax.all_to_all`` so each device computes
  full-sequence attention for a subset of heads, then the layout is
  restored.

Both run inside ``shard_map`` over a named mesh axis and are validated
against single-device reference attention on the CPU mesh.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from ..base import MXNetError

__all__ = ["ring_attention", "ulysses_attention", "reference_attention",
           "make_ring_attention"]


def reference_attention(q, k, v, causal: bool = False):
    """Plain full attention (B, T, H, D) — the correctness oracle, and
    Ulysses' local attention."""
    import jax.numpy as jnp

    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        t_q, t_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((t_q, t_k), dtype=bool))
        logits = jnp.where(mask, logits, -np.inf)
    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def ring_attention(q, k, v, axis_name: str, causal: bool = False):
    """Ring attention over sequence-sharded Q/K/V.

    Call inside ``shard_map``; ``q/k/v`` are the local shards
    (B, T_local, H, D) and ``axis_name`` the mesh axis carrying the
    sequence dimension.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    scale = 1.0 / np.sqrt(d)

    q_pos = my * t_local + jnp.arange(t_local)          # global query pos

    def step(i, carry):
        k_blk, v_blk, acc, m, l = carry
        src = (my - i) % n                               # owner of this K/V
        k_pos = src * t_local + jnp.arange(t_local)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]      # (t_q, t_k)
            logits = jnp.where(mask[None, None], logits, -jnp.inf)
        blk_max = logits.max(axis=-1)                    # (b,h,q)
        new_m = jnp.maximum(m, blk_max)
        # guard -inf rows (no valid keys yet) against NaNs
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        probs = jnp.exp(logits - safe_m[..., None])
        probs = jnp.where(jnp.isfinite(logits), probs, 0.0)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd",
                                                  probs, v_blk)
        l = l * alpha + probs.sum(axis=-1)
        # rotate K/V around the ring (ICI neighbor exchange)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, acc, new_m, l

    acc0 = jnp.zeros((b, h, t_local, d), q.dtype)
    m0 = jnp.full((b, h, t_local), -jnp.inf, q.dtype)
    l0 = jnp.zeros((b, h, t_local), q.dtype)
    _, _, acc, m, l = lax.fori_loop(0, n, step, (k, v, acc0, m0, l0))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3)                     # (b, t, h, d)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    Inside ``shard_map``: local shards (B, T_local, H, D) with H divisible
    by the axis size. all_to_all trades the sequence shard for a head
    shard, each device runs full-sequence attention on H/n heads, then the
    inverse all_to_all restores sequence sharding.
    """
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    b, t_local, h, d = q.shape
    if h % n:
        raise MXNetError("ulysses: num heads %d not divisible by axis %d"
                         % (h, n))

    def scatter_heads(x):
        # (b, t_local, h, d) -> (b, n*t_local, h/n, d)
        x = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                           tiled=True)
        return x

    def gather_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qf, kf, vf = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    return gather_heads(reference_attention(qf, kf, vf, causal=causal))


def make_ring_attention(mesh, axis_name: str = "sp", causal: bool = False,
                        impl: str = "ring"):
    """jit-able full-array entry point: takes global (B, T, H, D) arrays,
    shards T over ``axis_name`` and runs the chosen sequence-parallel
    attention under shard_map."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ._compat import shard_map

    fn = ring_attention if impl == "ring" else ulysses_attention
    spec = P(None, axis_name, None, None)
    body = functools.partial(fn, axis_name=axis_name, causal=causal)
    smapped = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec)

    @jax.jit
    def attn(q, k, v):
        return smapped(q, k, v)

    return attn
