"""Causal grouped-head attention with rotary positions, over every earlier
key or over a sliding window of them.

Beyond the reference (2016 MXNet has no attention operator); the op the
``*`` layers of a hybrid language model lower to. Layout as the other
language-model ops (``ops/seq.py``): ``[rows, width]`` activations whose
rows are whole sequences of ``seq_len`` positions.

**The mask** (counted ``lower.attention_mask.causal`` / ``.window`` once a
traced op): position ``i`` reads the keys ``j <= i``, or with ``window`` =
``w`` the band ``i - w < j <= i``: ``w`` keys, its own among them. A window
that holds the whole sequence (``w >= seq_len``) IS the causal op: the same
program. Under a band both lowerings below skip what it empties: the two
kernels visit the band's pairs of blocks, ``qi - ceil((w - 1) / block) <= ki
<= qi`` (the backward kernel's grid is the table of them,
``pallas_kernels.attention_block_pairs``; the forward kernel's is the query
blocks, a block's key blocks a loop inside its step; counted
``lower.attention_window.block_pairs`` beside what the causal half of the
same blocks holds, ``.block_pairs_causal``: 31 and 136 at 8,192 positions,
blocks of 512 and a window of 512), the pair on the diagonal masked above it,
the pairs the
band's lower edge crosses masked below it (with ``w`` = a block, half of the
one off-diagonal pair: the kernels then run at about half the causal ones'
share of their roofline, PERF.md section 6, PR 47), the pairs between
unmasked; the XLA body slices a query block's keys from the first its first
query reaches.

**Rotary frequencies** are the plain ``theta^(-2i/r)`` or, with
``rope_factor`` > 1, YaRN's blend of them and their ``1 / factor``
(:func:`rope_frequencies`), cos and sin times ``rope_attention_factor``;
either way they are float32 tables made once on the host from float64
angles, and ride the one relayout pass below.

Which implementation a program takes is decided from the shapes when it
is traced, and counted (``lower.attention_kernel.<name>``):

* ``pallas_splash``: the Pallas lowering, one kernel a pass in the layout
  JAX's splash attention (``jax.experimental.pallas.ops.tpu.
  splash_attention``, its multi-query form) takes, where that applies: a
  head of whole 128-lanes (128, 256: the Nemotron, Olmo, Laguna and
  GLM models) or of 64 columns, half a lane tile, with an even number of
  query and of key/value heads (the LFM2 model's 32 over 8; the kernels
  take a ``[T, 64]`` tile as it is, the 64 lanes beside it idle in their
  products, and the pass below moves the TWO heads that share a 128-lane
  tile of the rows in one grid step), and a sequence of whole
  ``SPLASH_BLOCK`` blocks. A head of one and a half lane tiles (192: the
  Ling model's latent layers, 128 content + 64 rotary columns) goes as a
  head of 256: 64 zero columns are put BEFORE each query's and key's own
  (rotary turns the last columns, and a zero column adds nothing to a
  score, so the result is exact; the scale stays ``192^-1/2``); the
  kernels' score products then run at 256, a third more than they need,
  and no other 192-wide path has to exist. Values may be narrower than
  keys (``value_dim``; whole lanes): the kernels take ``v`` at its own
  width, and the result is ``value_dim`` wide. A key/value head's ``G``
  query heads read that one head's keys, so nothing is repeated.
  **Which pass is whose**: both are this repo's wherever ONE rule,
  ``pallas_kernels.attention_applicable``, takes the shape, which is every
  shape the benchmark's cells trace, and both are JAX's splash kernels
  where it refuses (a sequence too long for a head's keys, values and
  gradient accumulators to stay in VMEM). The forward pass is
  ``pallas_kernels.attention_forward`` (counted ``lower.attention_forward.
  fused``, JAX's ``.splash``; device op ``causal_attention_forward`` /
  ``window_attention_forward``): the online softmax with a grid over the
  query blocks, a head's keys and values resident in VMEM and the band's
  key blocks a loop inside a query block's step, scores by key rows so that
  a position's running max, sum and log-sum-exp are one float32 number
  each, ``p`` rounded once to the compute dtype for ``p v``. The backward
  pass is ONE kernel over the table of block pairs (``pallas_kernels.
  attention_backward``, counted ``lower.attention_backward.fused``, JAX's
  two ``.split``: a pair of blocks' scores, ``exp`` and ``do v^T`` formed
  once for ``dq``, ``dk`` and ``dv``, five products where JAX's ``dq`` and
  ``dkv`` kernels run seven). Both passes are blockwise in their kernel
  (scores and softmax float32 in VMEM, products in the compute dtype,
  accumulators float32), blocks above the diagonal are
  skipped, and no ``[T, T]`` tensor reaches HBM in either pass. The XLA
  lowering below wrote its float32 scores out and read them back several
  times a pass: 577 of a 1,104 ms step at 8,192 tokens on the v5e
  (PERF.md, PR 26). The kernel wants ``[B, H, T, D]``, the graph holds
  ``[B*T, H*D]``: each operand goes there, and the result and every
  cotangent comes back, in ONE Pallas pass (``pallas_kernels.
  attention_relayout``) that reads a head's tile of
  rows where it lies and writes it where it belongs, row-major at both
  ends, so nothing is transposed and XLA lays the producers' results
  out for it. Rotary and the query's ``1/sqrt(D)`` ride that pass:
  float32 from the operand's dtype in VMEM, ``x * C + partner(x) * S``
  on the whole lanes that hold the turned columns, the scale, ONE
  rounding to the compute dtype; the backward pass is the transposed
  pass over the cotangent (the scale, then the rotation by the negative
  angle; a 64-wide head's partner is 32 columns away within its own half
  of the tile). No float32 copy of an operand reaches HBM. Counted
  ``lower.attention_layout.fused`` once a traced op: the one way there
  is, for every shape the kernel takes (XLA's own rotation, slices,
  concatenation and two-step transposes were 55 of the GLM cell's 379
  ms a step, and written as one elementwise chain or met in the
  kernel's ``SEQ_MINOR`` layout they compiled to MORE copies: PERF.md,
  PR 37).
* ``xla_blockwise``: everything else (heads of any other width, toy
  widths, an odd count of 64-wide heads), values of any width. Queries go
  in blocks of
  ``BLOCK_Q``; a block reads only the keys at or before its end, so the
  upper triangle is never computed, and each block runs under
  ``jax.checkpoint``: the backward pass recomputes one block's scores at
  a time and no ``[T, T]`` tensor exists in either pass. Scores, softmax
  and accumulation are float32, the two products take inputs in the
  compute dtype. The ``G`` query heads that share a key/value head ride
  one einsum against it.
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


def rope_frequencies(theta, half, scaling=None):
    """The ``half`` rotary frequencies of a turned width ``2 * half``,
    float64, and what cos and sin are multiplied by. ``scaling`` is ``None``
    (the plain ``theta^(-i / half)``, factor 1) or YaRN's ``(factor,
    original_positions, beta_fast, beta_slow, attention_factor)`` (Peng et
    al., arXiv:2309.00071, as the published ``_compute_yarn_parameters``
    writes it): a frequency that turns more than ``beta_fast`` times over
    the original context stays, one that turns less than ``beta_slow`` times
    is divided by ``factor``, and between the two columns where that happens
    (``floor`` / ``ceil``, within the turned width) the two are blended by a
    linear ramp; ``attention_factor`` 0 is ``0.1 ln(factor) + 1``."""
    plain = theta ** (-np.arange(half, dtype=np.float64) / half)
    if scaling is None:
        return plain, 1.0
    factor, original, beta_fast, beta_slow, attention_factor = scaling
    if not attention_factor:
        attention_factor = 0.1 * np.log(factor) + 1.0 if factor > 1 else 1.0
    if factor == 1:
        return plain, float(attention_factor)

    def column(turns):
        return 2 * half * np.log(original / (turns * 2 * np.pi)) \
            / (2 * np.log(theta))

    lo = max(np.floor(column(beta_fast)), 0)
    hi = min(np.ceil(column(beta_slow)), 2 * half - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - lo) / (hi - lo), 0, 1)
    return plain * (1 - ramp) + plain / factor * ramp, float(attention_factor)


@functools.lru_cache(None)
def rope_tables(t, theta, half, lanes=1, scaling=None):
    """``(C, S) [t, 2 * half]`` float32: ``C = [cos, cos]``, ``S = [-sin,
    sin]`` of the half-split convention, position by row; widened on the
    left with 1 and 0 to a whole number of ``lanes`` columns. ``scaling``:
    :func:`rope_frequencies`' (its attention factor is in both tables)."""
    inv, factor = rope_frequencies(theta, half, scaling)
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None]
    # float64 times 1.0 is itself: factor 1 gives the plain tables exactly
    cos = (np.cos(ang) * factor).astype(np.float32)
    sin = (np.sin(ang) * factor).astype(np.float32)
    pad = ((0, 0), (-2 * half % lanes, 0))
    return (np.pad(np.concatenate([cos, cos], 1), pad, constant_values=1),
            np.pad(np.concatenate([-sin, sin], 1), pad))


def relayout_tables(t, theta, half, head_dim, scaling=None):
    """:func:`rope_tables` as ``pallas_kernels.attention_relayout`` reads
    them: over whole 128-lane tiles of a head, or for a head of 64 columns
    one head's table beside the other's (two heads share a tile); nothing
    where nothing is turned."""
    if not half:
        return ()
    tables = rope_tables(t, theta, half, min(head_dim, 128), scaling)
    return tuple(np.tile(a, (1, max(1, 128 // head_dim))) for a in tables)


def rope(x, theta, scale=1.0, rotary_dim=0, pos_axis=0, scaling=None):
    """Rotary position embedding (Su et al., arXiv:2104.09864), the
    half-split convention of the published modelling code: ``x [T, ...,
    D]``, position = index along ``pos_axis``, over the whole head or, with
    ``rotary_dim``, over the LAST ``rotary_dim`` columns of it (the
    frequencies are those of a head ``rotary_dim`` wide, plain or under
    ``scaling``: :func:`rope_frequencies`; the columns before pass through).
    The result is multiplied by ``scale`` before it is rounded to ``x``'s
    dtype.

    Written ``x * C + partner(x) * S`` over the turned columns (``C, S``:
    :func:`rope_tables`; a column's partner is the one half the turned
    width away, and ``a + (-b) * s`` is ``a - b * s`` exactly): float32
    from ``x``'s dtype, one rounding. The pass that takes the splash
    kernel its operands (``pallas_kernels.attention_relayout``) does the
    same arithmetic on the tiles it moves."""
    import jax.numpy as jnp

    d = x.shape[-1]
    keep = d - rotary_dim if rotary_dim else 0
    half = (d - keep) // 2
    out = xf = x.astype(jnp.float32)
    if half:
        shape = [1] * x.ndim
        shape[pos_axis], shape[-1] = x.shape[pos_axis], 2 * half
        c, s = (a.reshape(shape)
                for a in rope_tables(x.shape[pos_axis], theta, half,
                                     scaling=scaling))
        r = xf[..., keep:]
        partner = jnp.concatenate([r[..., half:], r[..., :half]], axis=-1)
        out = r * c + partner * s
        if keep:
            out = jnp.concatenate([xf[..., :keep], out], axis=-1)
    return (out * scale if scale != 1.0 else out).astype(x.dtype)


@functools.lru_cache(None)
def _splash_kernel(t, group, block, interpret, keep_name, window=0):
    """JAX's multi-query splash kernel over ``group`` causal heads of ``t``
    positions, each reading every earlier key or, with ``window``, the last
    ``window`` (its own among them: ``LocalMask``, whose empty blocks the
    kernel skips as it skips those above the diagonal). It differentiates
    itself, through JAX's ``dq`` and ``dkv`` kernels."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks)

    import jax

    mask = masks.MultiHeadMask([
        masks.LocalMask((t, t), (window - 1, 0), 0) if window
        else masks.CausalMask((t, t)) for _ in range(group)])
    # the kernel's block tables are arrays made here: outside any trace,
    # or a cached kernel would carry one program's tracers into the next
    with jax.ensure_compile_time_eval():
        return kernel.make_splash_mqa_single_device(
            mask, block_sizes=kernel.BlockSizes(
                block_q=block, block_kv=block, block_kv_compute=block,
                block_q_dkv=block, block_kv_dkv=block,
                block_kv_dkv_compute=block, block_q_dq=block,
                block_kv_dq=block),
            interpret=interpret, residual_checkpoint_name=keep_name)


def attend_splash(q, k, v, keep_name=None, fused=True, window=0):
    """``q [B, Hkv, G, T, D]`` (already scaled by 1/sqrt(D)), ``k [B, Hkv,
    T, D]``, ``v [B, Hkv, T, Dv]`` -> ``[B, Hkv, G, T, Dv]``: a key/value
    head's ``G`` query heads against that one head's keys. The interpreter
    on ``cpu``, the Mosaic kernel elsewhere (``pallas_kernels.
    pallas_call``'s rule). Under ``keep_name`` the forward kernel's
    output and log-sum-exp, the backward pass's residuals, are marked for a
    recomputation to keep. ``window``: 0, every key at or before the
    query's position; ``w`` (below ``T``), the last ``w`` of them.

    ``fused`` (``pallas_kernels.attention_applicable``): both passes are
    this repo's, ``pallas_kernels.attention_forward`` and the ONE backward
    kernel of five products, ``pallas_kernels.attention_backward``, under a
    ``custom_vjp`` of this function's. Where the rule refuses a shape JAX's
    splash kernel runs, one call a (sequence, key/value head), and
    differentiates itself (its ``dq`` and ``dkv`` kernels: seven products,
    the same result in another order of the float32 sums)."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    from . import pallas_kernels

    if not fused:
        t, group = q.shape[3], q.shape[2]

        def run(interpret):
            one = _splash_kernel(t, group, min(SPLASH_BLOCK, t), interpret,
                                 keep_name, window)
            return lambda q, k, v: jax.vmap(jax.vmap(one))(q, k, v)

        return jax.lax.platform_dependent(q, k, v, cpu=run(True),
                                          default=run(False))

    @jax.custom_vjp
    def attend(q, k, v):
        return pallas_kernels.attention_forward(q, k, v, window)[0]

    def attend_fwd(q, k, v):
        out, lse = pallas_kernels.attention_forward(q, k, v, window)
        if keep_name is not None:
            # named HERE, in this rule's own forward pass, where a
            # recomputation's plan sees them: the kernel runs once a step
            out, lse = (checkpoint_name(x, keep_name) for x in (out, lse))
        return out, (q, k, v, out, lse)

    def attend_bwd(kept, do):
        q, k, v, out, lse = kept
        # a float32 sum over the head's columns: XLA reads both in the
        # compute dtype and writes one number a (head, position)
        di = jnp.einsum("bhgtd,bhgtd->bhgt", out.astype(jnp.float32),
                        do.astype(jnp.float32))
        return pallas_kernels.attention_backward(q, k, v, do, lse, di,
                                                 window)

    attend.defvjp(attend_fwd, attend_bwd)
    return attend(q, k, v)


def _block(q, k, v, start, scale, first=0, window=0):
    """Queries ``q [nq, Hkv, G, D]`` at positions ``start..`` against the
    keys ``k, v [nk, Hkv, D]`` at positions ``first..first + nk``; with
    ``window`` a query reads the last ``window`` keys at or before it."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("qhgd,khd->hgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = start + jnp.arange(q.shape[0])
    kpos = jnp.arange(k.shape[0])[None, :]
    if first:
        kpos = first + kpos
    mask = kpos <= qpos[:, None]
    if window:
        mask = mask & (kpos > qpos[:, None] - window)
    p = jax.nn.softmax(jnp.where(mask, s, _NEG), axis=-1)
    return jnp.einsum("hgqk,khd->qhgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def attend_blockwise(q, k, v, scale, block=BLOCK_Q, window=0):
    """One sequence: ``q [T, Hkv, G, D]``, ``k, v [T, Hkv, D]``. A block of
    queries reads the keys up to its end, under ``window`` from the first
    key its first query reaches."""
    import jax
    import jax.numpy as jnp

    t = q.shape[0]
    outs = []
    for i in range(0, t, block):
        n = min(i + block, t)
        first = max(0, i - window + 1) if window else 0
        fn = jax.checkpoint(functools.partial(
            _block, start=i, scale=scale, first=first, window=window))
        outs.append(fn(q[i:n], k[first:n], v[first:n]))
    return jnp.concatenate(outs, axis=0)


def _relaid(x, tables=(), back=False, **how):
    """``x`` through ``pallas_kernels.attention_relayout``, whose backward
    pass is the same pass over the cotangent, the other way."""
    import jax

    from .pallas_kernels import attention_relayout

    @jax.custom_vjp
    def f(x):
        return attention_relayout(x, tables, back=back, **how)

    f.defvjp(lambda x: (f(x), None),
             lambda _, g: (attention_relayout(g, tables, back=not back,
                                              **how),))
    return f(x)


@register_op("CausalAttention")
class CausalAttention(Operator):
    """``softmax(q k^T / sqrt(D) + causal mask) v`` per head, ``num_heads``
    query heads sharing ``num_kv_heads`` key/value heads (Ainslie et al.,
    GQA, arXiv:2305.13245) without repeating keys. ``rotary`` applies
    rotary positions to queries and keys first: over the whole head, or
    over its last ``rotary_dim`` columns (a head that is part content,
    part position: latent attention's 192 + 64). ``value_dim`` gives the
    values, and the result, a width of their own (latent attention whose
    values are narrower than its keys: 128 beside 128 + 64); 0, the
    default, is ``head_dim``. ``window`` ``w`` makes it sliding-window
    attention: position ``i`` reads the keys ``i - w < j <= i``, its own
    among the ``w``; 0, the default, and any ``w >= seq_len`` are plain
    causal attention, the same program. ``rope_factor`` above 1 scales the
    rotary frequencies by length as YaRN does (:func:`rope_frequencies`:
    ``rope_original_positions``, ``rope_beta_fast``, ``rope_beta_slow``,
    and ``rope_attention_factor`` on cos and sin)."""

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
        "value_dim": Param(int, 0, "a head's value (and result) width; 0: "
                           "head_dim"),
        "window": Param(int, 0, "a position reads the last window keys, its "
                        "own among them; 0: every earlier key"),
        "rope_factor": Param(float, 1.0, "YaRN: the length the frequencies "
                             "are scaled by; 1: the plain ones"),
        "rope_original_positions": Param(int, 0, "YaRN: the context the "
                                         "plain frequencies were trained "
                                         "at"),
        "rope_beta_fast": Param(float, 32.0),
        "rope_beta_slow": Param(float, 1.0),
        "rope_attention_factor": Param(float, 0.0, "YaRN: what cos and sin "
                                       "are multiplied by; 0: 0.1 "
                                       "ln(rope_factor) + 1"),
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
        if self.value_dim < 0:
            raise MXNetError("CausalAttention: value_dim %d"
                             % self.value_dim)
        if self.window < 0:
            raise MXNetError("CausalAttention: window %d" % self.window)
        if self.rope_factor < 1 or (self.rope_factor > 1
                                    and self.rope_original_positions < 1):
            raise MXNetError(
                "CausalAttention: rope_factor %g over %d original positions"
                % (self.rope_factor, self.rope_original_positions))
        _sequences(q[0], self.seq_len, "CausalAttention")
        vd = self.value_dim or self.head_dim
        return ([q, (q[0], self.num_kv_heads * self.head_dim),
                 (q[0], self.num_kv_heads * vd)],
                [(q[0], self.num_heads * vd)], [])

    def _splash_applies(self):
        t, d, vd = self.seq_len, self.head_dim, self.value_dim
        # whole lanes, or two heads to a 128-lane tile of the rows
        lanes = d % 128 == 0 or (
            d == 64 and self.num_heads % 2 == self.num_kv_heads % 2 == 0)
        if vd and vd != d:
            # values of their own width in whole lanes; a key of a tile
            # and a half is widened to two with zero columns
            lanes = vd % 128 == 0 and (d % 128 == 0 or d == 192)
        return lanes and t % 128 == 0 and t % min(SPLASH_BLOCK, t) == 0

    def _rope_scaling(self):
        """:func:`rope_frequencies`' ``scaling``; ``None``: the plain
        tables."""
        if self.rope_factor == 1 and self.rope_attention_factor in (0, 1):
            return None
        return (self.rope_factor, self.rope_original_positions,
                self.rope_beta_fast, self.rope_beta_slow,
                self.rope_attention_factor)

    def remat_results(self, in_shapes, in_types):
        """Kept always under recomputation: the kernel's output and, on
        the splash path, its float32 log-sum-exp a (head, position), the
        backward kernels' residuals: a step runs the forward kernel once.
        Projections and rotary are recomputed."""
        rows, width = in_shapes[0]
        if self.value_dim:
            width = self.num_heads * self.value_dim
        return [("attention", rows * width * np.dtype(in_types[0]).itemsize
                 + 4 * rows * self.num_heads, None)]

    def apply(self, ctx, inputs, aux):
        import jax
        import jax.numpy as jnp

        from .. import telemetry as _tel
        from . import pallas_kernels

        q, k, v = inputs
        t, hq, hkv, d = (self.seq_len, self.num_heads, self.num_kv_heads,
                         self.head_dim)
        vd = self.value_dim or d
        b = q.shape[0] // t
        scale = 1.0 / float(np.sqrt(d))
        half = (self.rotary_dim or d) // 2 if self.rotary else 0
        scaling = self._rope_scaling() if half else None
        # a window that holds the whole sequence is no window
        window = self.window if self.window < t else 0
        _tel.inc("lower.attention_mask.%s" % ("window" if window
                                              else "causal"))
        if not (self._splash_applies()
                and pallas_kernels.pallas_available()):
            _tel.inc("lower.attention_kernel.xla_blockwise")
            q = q.reshape(b, t, hkv, hq // hkv, d)
            k = k.reshape(b, t, hkv, d)
            if half:
                q, k = (rope(x, self.rope_theta, rotary_dim=2 * half,
                             pos_axis=1, scaling=scaling) for x in (q, k))
            out = jax.lax.map(
                lambda x: attend_blockwise(x[0], x[1], x[2], scale,
                                           window=window),
                (q, k, v.reshape(b, t, hkv, vd)))
            return [ctx.keep(out.reshape(b * t, hq * vd), "attention")], []
        _tel.inc("lower.attention_kernel.pallas_splash")
        _tel.inc("lower.attention_layout.fused")
        if window:
            # the pairs of blocks the backward pass walks under the band,
            # and what the causal half of the same blocks would hold
            block = min(pallas_kernels.ATTENTION_BACKWARD_BLOCK, t)
            blocks = t // block
            _tel.inc("lower.attention_window.block_pairs", len(
                pallas_kernels.attention_block_pairs(blocks, window,
                                                     block)[0]))
            _tel.inc("lower.attention_window.block_pairs_causal",
                     blocks * (blocks + 1) // 2)
        if d == 192:
            # a tile and a half: zero columns before each head's own make
            # it two (the docstring); the scale above is the true width's
            def widened(x, heads):
                x = jnp.pad(x.reshape(-1, heads, d), ((0, 0), (0, 0), (64, 0)))
                return x.reshape(-1, heads * (d + 64))

            q, k, d = widened(q, hq), widened(k, hkv), d + 64
        tables = relayout_tables(t, self.rope_theta, half, d, scaling)
        # the kernel takes queries already scaled: folded into the pass
        # that turns them, before its one rounding to the compute dtype
        q = _relaid(q, tables, batch=b, heads=hq, half=half, scale=scale)
        k = _relaid(k, tables, batch=b, heads=hkv, half=half)
        v = _relaid(v, batch=b, heads=hkv)
        # one rule, two kernels: this repo's forward and backward pass, or
        # JAX's splash kernel and its own two
        fused = pallas_kernels.attention_applicable(t, d, vd, q.dtype)
        _tel.inc("lower.attention_forward.%s"
                 % ("fused" if fused else "splash"))
        _tel.inc("lower.attention_backward.%s"
                 % ("fused" if fused else "split"))
        out = attend_splash(q.reshape(b, hkv, hq // hkv, t, d), k, v,
                            ctx.kept.get("attention"), fused, window)
        return [_relaid(out.reshape(b, hq, t, vd), back=True, batch=b,
                        heads=hq)], []
