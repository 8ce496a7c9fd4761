"""``CausalAttention``'s forward pass as one kernel of this repo's
(``pallas_kernels.attention_forward`` under ``attention.attend_splash``'s
``custom_vjp``), on the interpreter: the output and the log-sum-exp against
the float32 product and against JAX's splash forward kernel at the seven
language cells' head shapes cut to a few blocks, causal and under a band;
what its accumulator and its statistics hold; that the one attention rule's
shapes fit its step; what the operator counts; and the gradients through it
against JAX's kernels. What Mosaic makes of it at the cells' sizes is
``tests/test_chip_compile.py``'s; what it costs, the chip's
(``docs/pallas.md``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import attention
from mxnet_tpu.ops import pallas_kernels as pk

from test_attention_backward import gap, gradients, operands

# the cells' kinds of head: key/value heads, group, key and value columns
HEADS = {
    "glm-256-group-1": (2, 1, 256, 256),
    "laguna-128-group-6": (1, 6, 128, 128),
    "laguna-128-group-8": (1, 8, 128, 128),
    "lfm2-64-group-4": (2, 4, 64, 64),
    "ling-256-keys-128-values": (2, 1, 256, 128),
    "qwen3-next-256-group-8": (1, 8, 256, 256),
}
# causal at every kind of head; a band under, at and over a block of 512
# (the edge inside the diagonal block, ON the block behind it, across one
# and across two) at the kinds a windowed layer has: groups of whole-lane
# heads and a one-head group (two column parts of a query block, each
# reading only the key rows its queries can see) and 64-wide heads (one)
CASES = [(name, 0, "bfloat16") for name in HEADS] + [
    (name, window, "bfloat16")
    for name in ("laguna-128-group-8", "lfm2-64-group-4", "glm-256-group-1")
    for window in (300, 512, 640, 1100)] + [
    ("glm-256-group-1", 0, "float32"), ("lfm2-64-group-4", 0, "float32"),
    ("ling-256-keys-128-values", 0, "float32"),
    ("laguna-128-group-6", 640, "float32")]


def product(q, k, v, window=0):
    """``softmax(q k^T + mask) v`` and its log-sum-exp, float32 at the
    highest precision, the whole ``[T, T]`` scores at once."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    t = q.shape[3]
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhgtd,bhsd->bhgts", q, k)
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None]
        mask = (j <= i) & (j > i - window) if window else j <= i
        s = jnp.where(mask, s, -jnp.inf)
        lse = jax.nn.logsumexp(s, axis=-1)
        return jnp.einsum("bhgts,bhsd->bhgtd",
                          jnp.exp(s - lse[..., None]), v), lse


def splash_forward(q, k, v, window=0):
    """JAX's splash forward kernel under the mask and the blocks
    ``attend_splash`` gives it where the rule refuses a shape: the output
    and the log-sum-exp it keeps for its own backward kernels."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks)

    t, group = q.shape[3], q.shape[2]
    block = min(attention.SPLASH_BLOCK, t)
    one = kernel.make_splash_mqa_single_device(
        masks.MultiHeadMask([
            masks.LocalMask((t, t), (window - 1, 0), 0) if window
            else masks.CausalMask((t, t)) for _ in range(group)]),
        block_sizes=kernel.BlockSizes(block_q=block, block_kv=block,
                                      block_kv_compute=block),
        interpret=True, save_residuals=True)
    out, (lse,) = jax.jit(jax.vmap(jax.vmap(one)))(q, k, v)
    return out, lse


@pytest.mark.parametrize(
    "heads,window,dtype", CASES,
    ids=["%s-%s-%s" % (n, w or "causal", t) for n, w, t in CASES])
def test_the_forward_kernel_agrees_with_the_product_and_with_splash(
        heads, window, dtype):
    """Three blocks of 512 (six causal pairs; under a band of 300, 512, 640
    and 1,100 keys three, five, five and six): ``out`` and ``lse`` against
    the float32 product and against JAX's kernel under the same mask. The
    log-sum-exp is float32 statistics of float32 scores on both sides; the
    output differs from JAX's by the ONE rounding of ``p`` to the compute
    dtype before ``p v`` (JAX multiplies a float32 ``p``), inside a result's
    last bit."""
    hkv, group, d, dv = HEADS[heads]
    t = 1536
    assert pk.attention_applicable(t, d, dv, dtype)
    q, k, v, _ = operands(1, hkv, group, t, d, dv, dtype, seed=len(heads))
    out, lse = pk.attention_forward(q, k, v, window)
    assert out.shape == (1, hkv, group, t, dv) and out.dtype == q.dtype
    assert lse.shape == (1, hkv, group, t) and lse.dtype == jnp.float32
    want_out, want_lse = product(q, k, v, window)
    same_out, same_lse = splash_forward(q, k, v, window)
    # float32: the sums' order; bfloat16: a last bit of the result in the
    # largest values' binade (2^-7 of the value), from ``p``'s rounding
    near, far = (2e-6, 2e-6) if dtype == "float32" else (8e-3, 8e-3)
    assert gap(out, same_out) <= near
    assert gap(out, want_out) <= far
    assert gap(same_out, want_out) <= far
    assert float(jnp.abs(lse - same_lse).max()) <= 2e-6
    assert float(jnp.abs(lse - want_lse).max()) <= 2e-5


def online(q, k, v, block, acc, stat, prob):
    """The kernel's own arithmetic, a key block at a time, for ONE head
    ``q, k, v [T, D]`` and one query block (the last): running max and sum
    kept in ``stat``, the output's accumulator in ``acc``, ``p`` rounded to
    ``prob``. Returns the last query block's output (before its one
    rounding) and log-sum-exp."""
    f32 = jnp.float32
    t = q.shape[0]
    qb = q[t - block:].astype(f32)
    m = jnp.full((block, 1), pk._ATTENTION_MASKED, stat)
    l = jnp.zeros((block, 1), stat)
    o = jnp.zeros((block, v.shape[1]), acc)
    with jax.default_matmul_precision("highest"):
        for j in range(t // block):
            rows = slice(j * block, (j + 1) * block)
            s = qb @ k[rows].astype(f32).T
            if j == t // block - 1:
                s = jnp.where(jnp.tril(jnp.ones((block, block), bool)), s,
                              pk._ATTENTION_MASKED)
            m_next = jnp.maximum(m.astype(f32), s.max(-1, keepdims=True))
            alpha = jnp.exp(m.astype(f32) - m_next)
            p = jnp.exp(s - m_next)
            l = (alpha * l.astype(f32) + p.sum(-1, keepdims=True)).astype(
                stat)
            m = m_next.astype(stat)
            o = (alpha * o.astype(f32)
                 + p.astype(prob).astype(f32) @ v[rows].astype(f32)).astype(
                     acc)
    l = l.astype(f32)
    return o.astype(f32) / l, (m.astype(f32) + jnp.log(l))[:, 0]


def test_the_accumulator_and_the_statistics_are_float32_across_sixteen_key_blocks(
        monkeypatch):
    """A bfloat16 run over 16 key blocks, the last query block's rows: the
    output equals the float32-accumulated sum of the kernel's own products
    (``p`` rounded once to bfloat16, its float32 products with ``v`` added
    and rescaled in float32) to ONE rounding of the result in all but one
    element in a thousand, which an accumulator kept in bfloat16 from block
    to block misses in a fifth of the elements; the log-sum-exp equals the
    float32 statistics' to 1e-5, which a running max and sum kept in
    bfloat16 miss by a hundred times that."""
    monkeypatch.setattr(pk, "ATTENTION_FORWARD_BLOCK", 128)
    t, d, block, bf, f32 = 2048, 128, 128, jnp.bfloat16, jnp.float32
    q, k, v, _ = operands(1, 1, 1, t, d, d, bf, seed=3)
    # scores of some spread, so that the running max moves from block to
    # block and every rescale is a real one
    q = (q.astype(f32) * 4).astype(bf)
    out, lse = jax.jit(pk._attention_forward)(q, k, v)
    mine = np.asarray(out[0, 0, 0, t - block:], np.float32)
    mine_lse = np.asarray(lse[0, 0, 0, t - block:])
    head = (q[0, 0, 0], k[0, 0], v[0, 0])
    whole, whole_lse = (np.asarray(x) for x in online(*head, block, f32, f32,
                                                      bf))
    narrow, _ = (np.asarray(x) for x in online(*head, block, bf, f32, bf))
    _, coarse_lse = (np.asarray(x) for x in online(*head, block, f32, bf,
                                                   bf))
    # one rounding: half a last place (at most 2^-8 of the value), and the
    # float32 sums' own order
    bound = 2.0 ** -8 * np.abs(whole) + 1e-6 * np.abs(whole).max()
    assert (np.abs(mine - whole) > bound).mean() < 1e-3
    assert (np.abs(narrow - whole) > bound).mean() > 0.2
    assert np.abs(mine_lse - whole_lse).max() < 1e-5
    assert np.abs(coarse_lse - whole_lse).max() > 1e-3


def equations(jaxpr):
    """Every equation of ``jaxpr`` and of the programs its equations hold
    (the kernel's body, its ``cond`` branches, the interpreter's and
    Mosaic's copies of the call)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for held in value if isinstance(value, (tuple, list)) \
                    else (value,):
                held = getattr(held, "jaxpr", held)
                if hasattr(held, "eqns"):
                    yield from equations(held)


@pytest.mark.parametrize("window", [0, 640], ids=["causal", "window-640"])
def test_the_products_take_the_compute_dtype_and_the_rest_is_float32(window):
    """The kernel as it is traced for bfloat16 operands: both products of
    every kind of pair take bfloat16 operands (``v`` as it lies, ``p``
    rounded) and give float32 (``preferred_element_type``); every ``exp``,
    maximum, sum and logarithm is float32; nothing but ``p``, the result
    and the transposed ``v`` block is bfloat16."""
    q, k, v, _ = operands(1, 1, 2, 2048, 128, 128, "bfloat16")
    eqns = list(equations(jax.make_jaxpr(
        lambda *a: pk._attention_forward(*a, window=window))(q, k, v).jaxpr))
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert dots and len(dots) % 2 == 0
    for e in dots:
        assert [x.aval.dtype for x in e.invars] == [jnp.bfloat16] * 2
        assert e.outvars[0].aval.dtype == jnp.float32
    wide = [e for e in eqns if e.primitive.name in (
        "exp", "log", "reduce_max", "reduce_sum", "max", "div")]
    assert {e.primitive.name for e in wide} >= {
        "exp", "log", "reduce_max", "reduce_sum", "max", "div"}
    for e in wide:
        assert e.outvars[0].aval.dtype in (jnp.float32, jnp.int32), e
    narrow = {e.primitive.name for e in eqns for x in e.outvars
              if getattr(x.aval, "dtype", None) == jnp.bfloat16
              and e.primitive.name not in ("pallas_call", "cond", "jit",
                                           "pjit", "platform_index")}
    assert narrow <= {"get", "swap", "slice", "convert_element_type",
                      "transpose"}, narrow


@pytest.mark.parametrize("t,d,dv,dtype,group,takes", [
    (8192, 256, 256, "bfloat16", 8, True),      # GLM 1, Qwen3-Next 8
    (8192, 64, 64, "bfloat16", 4, True),        # LFM2
    (8192, 256, 128, "bfloat16", 1, True),      # Ling, keys widened
    (8192, 128, 128, "bfloat16", 16, True),     # Nemotron 16, Laguna 6, 8
    (8192, 128, 128, "float32", 6, True),
    (128, 128, 128, "float32", 1, True),        # one block
    (2048, 1024, 1024, "float32", 8, True),     # four heads a step, not 8
    (65536, 256, 256, "bfloat16", 1, False),    # a head's arrays past VMEM
    (8192, 256, 256, "float16", 1, False),      # not the MXU's
    (640, 128, 128, "bfloat16", 1, False),      # part blocks
    (8192, 128, 96, "bfloat16", 1, False)],     # part lanes
    ids=["256-wide", "64-wide", "values-narrower", "128-wide", "float32",
         "one-block", "wide", "too-long", "float16", "part-blocks",
         "part-lanes"])
def test_what_the_rule_takes_fits_the_forward_kernels_step(
        t, d, dv, dtype, group, takes):
    """ONE rule for the two kernels (``attend_splash`` joins them under one
    ``custom_vjp``): where it takes a shape, the forward kernel's step (a
    head's keys and values resident, the query heads it runs side by side
    with their blocks and accumulators) is within the VMEM asked for at the
    number of heads the kernel takes, a divisor of the group up to eight;
    where it refuses, JAX's three kernels run."""
    assert pk.attention_applicable(t, d, dv, dtype) is takes
    if not takes:
        return
    block = min(pk.ATTENTION_FORWARD_BLOCK, t)
    itemsize = jnp.dtype(dtype).itemsize
    heads = pk._attention_forward_heads(group, t, d, dv, itemsize, block)
    assert group % heads == 0 and 1 <= heads <= 8
    assert pk._attention_forward_vmem(heads, t, d, dv, itemsize, block) \
        <= pk._ATTENTION_VMEM - (8 << 20)
    assert heads == min(group, 8) or pk._attention_forward_vmem(
        2 * heads, t, d, dv, itemsize, block) > pk._ATTENTION_VMEM - (8 << 20)


@pytest.mark.parametrize("fits", [True, False], ids=["fused", "splash"])
def test_the_operator_counts_which_forward_pass_it_takes(monkeypatch, fits):
    """``CausalAttention`` asks the one rule once a traced node and counts
    the answer for both passes beside the kernel's and the layout's: this
    repo's two kernels, or JAX's forward kernel with its own two backward
    kernels; the result and the gradients agree with the XLA body's either
    way."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.registry import OpContext, create_operator

    if not fits:
        monkeypatch.setattr(pk, "attention_applicable", lambda *a: False)
    t, heads, kv, d = 256, 4, 2, 128
    rng = np.random.default_rng(1)
    inputs = [jnp.asarray(rng.standard_normal((t, n * d)), jnp.float32)
              for n in (heads, kv, kv)]
    op = create_operator("CausalAttention", num_heads=heads, num_kv_heads=kv,
                         head_dim=d, seq_len=t)

    def loss(inputs):
        out = op.apply(OpContext(True), list(inputs), [])[0][0]
        return (out * jnp.linspace(0.5, 1.5, out.size).reshape(
            out.shape)).sum()

    names = ("attention_forward.fused", "attention_forward.splash",
             "attention_backward.fused", "attention_backward.split",
             "attention_kernel.pallas_splash")
    telemetry.reset()
    telemetry.enable()
    try:
        traced = jax.jit(jax.value_and_grad(loss)).trace(inputs)
        counted = {name: telemetry.peek("lower." + name) or 0
                   for name in names}
    finally:
        telemetry.reset()
        telemetry.disable()
    assert counted == dict(zip(names, (
        int(fits), int(not fits), int(fits), int(not fits), 1)))
    text = str(traced.jaxpr)
    assert ("causal_attention_forward" in text) is fits
    assert ("splash_mqa_fwd" in text) is not fits
    value, grads = traced.lower().compile()(inputs)
    monkeypatch.setattr(pk, "pallas_available", lambda: False)
    want_value, want = jax.jit(jax.value_and_grad(loss))(inputs)
    assert abs(float(value) - float(want_value)) <= 1e-5 * abs(
        float(want_value))
    for got, ref in zip(grads, want):
        assert gap(got, ref) <= 1e-5


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,hkv,group,t,d,dv,window", [
    (1, 2, 1, 384, 128, 128, 0), (1, 2, 4, 256, 64, 64, 0),
    (1, 1, 2, 384, 256, 128, 0), (2, 1, 2, 256, 256, 256, 0),
    (1, 1, 6, 512, 128, 128, 160), (1, 2, 4, 512, 64, 64, 280)],
    ids=["one-head-groups-of-128", "groups-of-four-heads-of-64",
         "values-narrower-than-keys", "two-sequences-of-256-wide-groups",
         "groups-of-six-window-160", "heads-of-64-window-280"])
def test_gradients_through_the_forward_kernel_agree_with_the_split_path(
        monkeypatch, b, hkv, group, t, d, dv, window, dtype):
    """``dq``, ``dk``, ``dv`` of ``attend_splash`` with this repo's two
    kernels (the backward kernel reads THIS forward kernel's ``out`` and
    ``lse``) against JAX's three (``fused=False``), over blocks of 128."""
    for name in ("ATTENTION_FORWARD_BLOCK", "ATTENTION_BACKWARD_BLOCK"):
        monkeypatch.setattr(pk, name, 128)
    monkeypatch.setattr(attention, "SPLASH_BLOCK", 128)
    q, k, v, do = operands(b, hkv, group, t, d, dv, dtype)
    ours = gradients(
        lambda *a: attention.attend_splash(*a, window=window), q, k, v, do)
    split = gradients(
        lambda *a: attention.attend_splash(*a, window=window, fused=False),
        q, k, v, do)
    near = 2e-6 if dtype == "float32" else 8e-3
    for got, same in zip(ours, split):
        assert got.dtype == same.dtype == jnp.dtype(dtype)
        assert gap(got, same) <= near
