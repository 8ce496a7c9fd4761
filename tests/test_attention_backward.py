"""``CausalAttention``'s backward pass as one kernel of five products
(``pallas_kernels.attention_backward`` under ``attention.attend_splash``'s
``custom_vjp``), on the interpreter: against ``jax.grad`` of the float32
product, against JAX's own ``dq`` and ``dkv`` kernels, what its
accumulators hold, which pairs of blocks it visits and which shapes its
rule admits. What Mosaic makes of it at the cells' sizes is
``tests/test_chip_compile.py``'s; what it costs, the chip's
(``docs/pallas.md``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import attention
from mxnet_tpu.ops import pallas_kernels as pk


def operands(b, hkv, group, t, d, dv, dtype, seed=0):
    """``q`` (scaled), ``k``, ``v`` and a cotangent of the output."""
    rng = np.random.default_rng(seed)

    def put(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    return (put(b, hkv, group, t, d, scale=d ** -0.5), put(b, hkv, t, d),
            put(b, hkv, t, dv), put(b, hkv, group, t, dv))


def gradients(attend, q, k, v, do):
    """``dq, dk, dv`` of ``sum(attend(q, k, v) * do)``."""
    def loss(q, k, v):
        return (attend(q, k, v).astype(jnp.float32)
                * do.astype(jnp.float32)).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


def blockwise(q, k, v):
    """The float32 body of the ``xla_blockwise`` path, a sequence at a
    time: ``attend_blockwise`` wants ``[T, Hkv, G, D]``."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    out = jax.vmap(lambda q, k, v: attention.attend_blockwise(
        q.transpose(2, 0, 1, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
        1.0, block=128))(q, k, v)
    return out.transpose(0, 2, 3, 1, 4)


def gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,hkv,group,t,d,dv", [
    (1, 2, 1, 384, 128, 128), (1, 2, 4, 256, 64, 64),
    (1, 1, 2, 384, 256, 128), (2, 1, 2, 256, 256, 256)],
    ids=["one-head-groups-of-128", "groups-of-four-heads-of-64",
         "values-narrower-than-keys", "two-sequences-of-256-wide-groups"])
def test_one_kernel_backward_agrees_with_the_product_and_the_split_kernels(
        monkeypatch, b, hkv, group, t, d, dv, dtype):
    """``dq``, ``dk``, ``dv`` of the one-kernel pass at one small shape of
    each kind its rule admits, over two or three blocks of 128 (pairs under,
    on and skipped above the diagonal): against ``jax.grad`` of the float32
    blockwise body, and against JAX's ``dq`` and ``dkv`` kernels, which form
    the same five products' operands in seven."""
    monkeypatch.setattr(pk, "ATTENTION_BACKWARD_BLOCK", 128)
    assert pk.attention_applicable(t, d, dv, dtype)
    q, k, v, do = operands(b, hkv, group, t, d, dv, dtype)
    fused = gradients(attention.attend_splash, q, k, v, do)
    split = gradients(lambda *a: attention.attend_splash(*a, fused=False),
                      q, k, v, do)
    exact = gradients(blockwise, q, k, v, do)
    # float32: sums in another order; bfloat16: the rounding of ``p``,
    # ``ds`` and each result (2^-8), the split kernels' own distance from
    # the product, and between the two a result's last bit
    near, far = (2e-6, 2e-6) if dtype == "float32" else (8e-3, 2.5e-2)
    for got, same, want in zip(fused, split, exact):
        assert got.dtype == same.dtype == jnp.dtype(dtype)
        assert got.shape == same.shape
        assert gap(got, same) <= near
        assert gap(got, want) <= far
        assert gap(same, want) <= far


def test_the_accumulators_are_float32_across_sixteen_key_blocks(monkeypatch):
    """A bfloat16 run over 16 key blocks: ``dq``, ``dk`` and ``dv`` equal
    the float32-accumulated sums of the kernel's own products to ONE
    rounding of the result (half a last place of bfloat16) in all but one
    element in a thousand (where a ``p`` or ``ds`` of the kernel's own
    scores rounds the other way), which sums kept in bfloat16 from block to
    block miss in a third of the elements."""
    monkeypatch.setattr(pk, "ATTENTION_BACKWARD_BLOCK", 128)
    t, d, block, bf, f32 = 2048, 128, 128, jnp.bfloat16, jnp.float32
    q, k, v, do = operands(1, 1, 1, t, d, d, bf, seed=3)
    qf, kf, vf, dof = (x[0, 0].reshape(t, d).astype(f32)
                       for x in (q, k, v, do))
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), qf @ kf.T, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[:, None])
    di = ((p @ vf) * dof).sum(-1)
    got = jax.jit(pk.attention_backward)(
        q, k, v, do, lse.reshape(1, 1, 1, t), di.reshape(1, 1, 1, t))
    # the kernel's products a block, operands rounded where it rounds them
    ds = ((dof @ vf.T - di[:, None]) * p).astype(bf).astype(f32)
    pb = p.astype(bf).astype(f32)
    n = t // block
    parts = {
        "dq": [ds[:, j * block:(j + 1) * block]
               @ kf[j * block:(j + 1) * block] for j in range(n)],
        "dk": [ds[i * block:(i + 1) * block].T
               @ qf[i * block:(i + 1) * block] for i in range(n)],
        "dv": [pb[i * block:(i + 1) * block].T
               @ dof[i * block:(i + 1) * block] for i in range(n)]}
    for name, mine in zip(("dq", "dk", "dv"), got):
        mine = np.asarray(mine[0, 0].reshape(t, d), np.float32)
        whole = np.asarray(sum(parts[name]), np.float32)
        narrow = jnp.zeros((t, d), bf)
        for part in parts[name]:
            narrow = (narrow.astype(f32) + part).astype(bf)
        narrow = np.asarray(narrow, np.float32)
        # one rounding: half a last place (2^-9 of the value's binade, at
        # most 2^-8 of the value), and the float32 sums' own order
        bound = 2.0 ** -8 * np.abs(whole) + 1e-6 * np.abs(whole).max()
        assert (np.abs(mine - whole) > bound).mean() < 1e-3, name
        assert (np.abs(narrow - whole) > bound).mean() > 0.2, name


@pytest.mark.parametrize("blocks", [1, 2, 5, 16])
def test_the_pairs_of_blocks_are_the_causal_half(blocks):
    """The prefetched table: every pair of blocks at or under the diagonal
    and no other, a query block's pairs together and in key order, so that
    its first pair is key block 0 (``dq`` starts) and its last the one ON
    the diagonal (the mask is paid, ``dq`` is written)."""
    qs, ks = pk.attention_block_pairs(blocks)
    assert list(zip(qs.tolist(), ks.tolist())) == [
        (i, j) for i in range(blocks) for j in range(i + 1)]
    assert qs.dtype == ks.dtype == np.int32
    if blocks == 16:        # 8,192 positions in blocks of 512
        assert len(qs) == 136 and int((qs == ks).sum()) == 16


@pytest.mark.parametrize("t,d,dv,dtype,takes", [
    (8192, 256, 256, "bfloat16", True),     # GLM, Qwen3-Next
    (8192, 64, 64, "bfloat16", True),       # LFM2
    (8192, 256, 128, "bfloat16", True),     # Ling, keys widened
    (8192, 128, 128, "bfloat16", True),     # Nemotron, Olmo
    (8192, 128, 128, "float32", True),
    (128, 128, 128, "float32", True),       # one block
    (8192, 256, 256, "float16", False),     # not the MXU's
    (65536, 256, 256, "bfloat16", False),   # accumulators past VMEM
    (640, 128, 128, "bfloat16", False),     # part blocks
    (8192, 128, 96, "bfloat16", False)],    # part lanes
    ids=["256-wide", "64-wide", "values-narrower", "128-wide", "float32",
         "one-block", "float16", "too-long", "part-blocks", "part-lanes"])
def test_the_rule_takes_the_cells_shapes_and_refuses_what_does_not_fit(
        t, d, dv, dtype, takes):
    assert pk.attention_applicable(t, d, dv, dtype) is takes


@pytest.mark.parametrize("fits", [True, False], ids=["fused", "split"])
def test_the_operator_counts_which_backward_pass_it_takes(monkeypatch, fits):
    """``CausalAttention`` asks the rule once a traced node and counts the
    answer beside the kernel's and the layout's; where the rule refuses,
    JAX's kernel differentiates itself and the gradients agree."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.registry import OpContext, create_operator

    if not fits:
        monkeypatch.setattr(pk, "attention_applicable",
                            lambda *a: False)
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

    telemetry.reset()
    telemetry.enable()
    try:
        grads = jax.jit(jax.grad(loss))(inputs)
        counted = {name: telemetry.peek("lower.attention_backward." + name)
                   or 0 for name in ("fused", "split")}
    finally:
        telemetry.reset()
        telemetry.disable()
    assert counted == {"fused": int(fits), "split": int(not fits)}
    monkeypatch.setattr(pk, "pallas_available", lambda: False)
    want = jax.jit(jax.grad(loss))(inputs)
    for got, ref in zip(grads, want):
        assert gap(got, ref) <= 1e-5
