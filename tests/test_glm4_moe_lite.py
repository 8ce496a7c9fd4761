"""``models.get_glm4_moe_lite`` (latent attention: low-rank queries and
keys/values, a shared rotary key, rotary over a part of the head; gated
routed experts behind a leading dense layer) through ``Module.fit`` on the
fused step against the benchmark's float32 reference; the operators' new
forms (``CausalAttention(rotary_dim=...)``, ``RoutedExperts(gated=True)``)
against plain ``jax.numpy``; the share by experts of ``model-configs``
section 4; and the programs of the two models that share those operators,
which must lower to the text they lowered to before. Toy widths, seeded."""
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu import telemetry
from mxnet_tpu.models import (get_glm4_moe_lite, get_nemotron_h,
                              get_olmo_hybrid)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import glm4_moe_lite as ref  # noqa: E402
from benchmark.reference import nemotron_h, olmo_hybrid  # noqa: E402
from test_hlo_gates import (check_products_are_bfloat16,  # noqa: E402
                            check_state_is_donated, lower_language_toy)
from test_nemotron_h import (Ring, against, aux_states, close,  # noqa: E402
                             rng_inputs, run_op)
from test_nemotron_h import TOY as NEMOTRON_TOY  # noqa: E402
from test_olmo_hybrid import TOY as OLMO_TOY  # noqa: E402
from test_olmo_hybrid import toy_batches  # noqa: E402

TOY = dict(layers=3, dense_layers=1, hidden=32, vocab=96, heads=4, q_rank=16,
           kv_rank=8, nope_dim=12, rope_dim=4, v_dim=16, dense_hidden=48,
           experts_total=8, experts_held=4, first_expert=2, top_k=2,
           expert_hidden=24, seq_len=24, bias_update_rate=0.01)
RECIPE = {"learning_rate": 0.001, "wd": 0.01, "beta1": 0.9, "beta2": 0.95,
          "epsilon": 1e-8, "rescale_grad": 1.0}


# ---------------------------------------------------------------------------
# the operators' new forms
# ---------------------------------------------------------------------------
def plain_attention(query, key, value, heads, head_dim, rotary_dim, theta):
    """Dense causal softmax a head, the rotation of the last ``rotary_dim``
    columns written out position by position (half-split pairs)."""
    t = query.shape[0]
    q, k, v = (x.reshape(t, heads, head_dim) for x in (query, key, value))
    half, keep = rotary_dim // 2, head_dim - rotary_dim
    ang = np.arange(t)[:, None] * theta ** (-np.arange(half) / half)[None]
    cos, sin = (jnp.asarray(f(ang), jnp.float32)[:, None, :]
                for f in (np.cos, np.sin))

    def turn(x):
        a, b = x[..., keep:keep + half], x[..., keep + half:]
        return jnp.concatenate([x[..., :keep], a * cos - b * sin,
                                b * cos + a * sin], axis=-1)

    s = jnp.einsum("qhd,khd->hqk", turn(q), turn(k)) / np.sqrt(head_dim)
    s = jnp.where(np.tril(np.ones((t, t), bool)), s, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return out.reshape(t, heads * head_dim)


@pytest.mark.parametrize("head_dim,rotary_dim,t,kernel", [
    (256, 64, 128, "pallas_splash"), (24, 8, 16, "xla_blockwise")],
    ids=["256-wide", "not-whole-lanes"])
def test_attention_rotates_a_part_of_the_head(head_dim, rotary_dim, t,
                                              kernel):
    """``rotary_dim``: rotary positions over the LAST columns of each head,
    queries and keys alike, the rest passing through: forward and gradient,
    through the splash kernel (interpreted here) at heads of 256 and through
    the XLA lowering at a width that is no whole lane."""
    heads = 2
    inputs = rng_inputs(3, query=(t, heads * head_dim),
                        key=(t, heads * head_dim),
                        value=(t, heads * head_dim))
    v = {k: sym.Variable(k) for k in inputs}
    net = sym.CausalAttention(num_heads=heads, num_kv_heads=heads,
                              head_dim=head_dim, seq_len=t, rope_theta=1e4,
                              rotary_dim=rotary_dim, **v)
    telemetry.reset()
    telemetry.enable()
    try:
        against(lambda **kw: plain_attention(
            heads=heads, head_dim=head_dim, rotary_dim=rotary_dim,
            theta=1e4, **kw), net, inputs, tol=2e-4)
        assert telemetry.peek("lower.attention_kernel." + kernel) >= 1
    finally:
        telemetry.disable()


def test_rotary_dim_of_the_whole_head_is_the_whole_head():
    from mxnet_tpu.ops import attention

    x = jnp.asarray(rng_inputs(1, x=(8, 3, 16))["x"])
    whole = attention.rope(x, 1e4)
    np.testing.assert_array_equal(attention.rope(x, 1e4, rotary_dim=16),
                                  whole)
    part = attention.rope(x, 1e4, rotary_dim=4)
    np.testing.assert_array_equal(part[..., :12], x[..., :12])
    np.testing.assert_array_equal(part[..., 12:],
                                  attention.rope(x[..., 12:], 1e4))
    with pytest.raises(mx.MXNetError, match="rotary_dim 5"):
        sym.CausalAttention(query=sym.Variable("q"), num_heads=1,
                            num_kv_heads=1, head_dim=8, seq_len=4,
                            rotary_dim=5).infer_shape(q=(4, 8))


def split_rope(x, theta, scale=1.0, rotary_dim=0):
    """``ops.attention.rope`` as it was before the rotation was written
    over the turned columns in one piece (PR 37): the two halves cut out,
    turned apart and put back. The reference the new form has to equal."""
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


@pytest.mark.parametrize("head_dim,rotary_dim,dtype", [
    (256, 64, "bfloat16"), (128, 0, "bfloat16"), (16, 4, "float32"),
    (16, 16, "float32")])
def test_rope_is_the_split_form_bit_for_bit(head_dim, rotary_dim, dtype):
    """``x * C + partner(x) * S`` over the turned columns is the split and
    concatenated rotation to the bit, with and without the scale folded
    in, and so is its cotangent (to float32 rounding at most)."""
    from mxnet_tpu.ops import attention

    x, g = (jnp.asarray(v, dtype) for v in rng_inputs(
        2, x=(64, 3, head_dim), g=(64, 3, head_dim)).values())
    for scale in (1.0, 1.0 / float(np.sqrt(head_dim))):
        got, back = jax.vjp(lambda x: attention.rope(
            x, 1e4, scale, rotary_dim), x)
        want, want_back = jax.vjp(lambda x: split_rope(
            x, 1e4, scale, rotary_dim), x)
        assert got.dtype == want.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        np.testing.assert_allclose(
            np.asarray(back(g)[0], np.float32),
            np.asarray(want_back(g)[0], np.float32), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("batch,t,heads,head_dim,turned,dtype", [
    (1, 256, 2, 256, 64, "bfloat16"), (2, 128, 3, 128, 128, "bfloat16"),
    (1, 128, 2, 128, 0, "bfloat16"), (1, 256, 2, 256, 192, "float32"),
    (3, 128, 1, 128, 2, "float32")],
    ids=["glm", "nemotron", "plain", "tile-and-a-half", "one-pair"])
def test_the_pass_to_the_kernel_is_rope_and_a_transpose(batch, t, heads,
                                                        head_dim, turned,
                                                        dtype):
    """The Pallas pass that takes the splash kernel its operands (the
    interpreter here) against ``rope`` with the scale folded in and a
    transpose to ``[B, H, T, D]``, and its backward pass against theirs:
    the way there equal to the bit in ``bfloat16`` (one rounding of the
    same float32 arithmetic), everything else to a rounding."""
    from mxnet_tpu.ops import attention

    half, scale = turned // 2, 0.125
    tables = attention.rope_tables(t, 1e4, half, 128) if half else ()
    shape = (batch, t, heads, head_dim)
    x = jnp.asarray(rng_inputs(3, x=(batch * t, heads * head_dim))["x"],
                    dtype)
    g = jnp.asarray(rng_inputs(4, g=(batch, heads, t, head_dim))["g"], dtype)
    got, back = jax.vjp(lambda x: attention._relaid(
        x, tables, batch=batch, heads=heads, half=half, scale=scale), x)
    want, want_back = jax.vjp(lambda x: attention.rope(
        x.reshape(shape), 1e4, scale, turned, pos_axis=1).transpose(
            0, 2, 1, 3) if half else (x.reshape(shape).astype(
                jnp.float32) * scale).astype(dtype).transpose(0, 2, 1, 3), x)
    # autodiff of ``rope`` adds the cotangent's parts in another order: a
    # float32 rounding apart, which a rounding to ``bfloat16`` can widen
    # to one of its own
    exact = dtype == "bfloat16"
    for a, b, tol in ((got, want, 0 if exact else 1e-6),
                      (back(g)[0], want_back(g)[0], 2 ** -7 if exact
                       else 1e-6)):
        assert a.dtype == b.dtype == x.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=tol,
                                   atol=tol * 1e-2)
    # the way back undoes the way there where nothing is turned or scaled
    there = attention._relaid(x, batch=batch, heads=heads)
    np.testing.assert_array_equal(
        np.asarray(attention._relaid(there, back=True, batch=batch,
                                     heads=heads), np.float32),
        np.asarray(x, np.float32))


def _attention_over(monkeypatch, splash, inputs, head, **op):
    """One traced ``CausalAttention`` through bind / forward / backward on
    the path asked for, and what the lowering counted."""
    from mxnet_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "pallas_available", lambda: splash)
    net = sym.CausalAttention(**op, **{k: sym.Variable(k) for k in inputs})
    telemetry.reset()
    telemetry.enable()
    try:
        return run_op(net, inputs, head), {
            n: telemetry.peek("lower." + n) for n in (
                "attention_kernel.pallas_splash",
                "attention_kernel.xla_blockwise", "attention_layout.fused",
                "attention_layout.split")}
    finally:
        telemetry.disable()


@pytest.mark.parametrize("heads,kv_heads,head_dim,rotary", [
    (2, 2, 256, dict(rotary_dim=64, rope_theta=1e6)),
    (4, 2, 128, dict(rope_theta=1e4)), (3, 3, 128, dict(rotary=False))],
    ids=["glm", "nemotron", "olmo"])
def test_the_splash_path_agrees_with_the_xla_path(monkeypatch, heads,
                                                  kv_heads, head_dim,
                                                  rotary):
    """The three models' heads at 512 positions: operands turned and laid
    out by the Pallas passes and attended by the splash kernel (all
    interpreted here) against the XLA lowering: the output and the three
    input gradients; and the passes are counted once a traced op."""
    t = 512
    inputs = rng_inputs(7, query=(t, heads * head_dim),
                        key=(t, kv_heads * head_dim),
                        value=(t, kv_heads * head_dim))
    head = rng_inputs(8, h=(t, heads * head_dim))["h"]
    op = dict(num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
              seq_len=t, **rotary)
    (want, want_g), counted = _attention_over(monkeypatch, False, inputs,
                                              head, **op)
    assert counted["attention_kernel.xla_blockwise"] >= 1
    assert not counted["attention_layout.fused"]
    (got, got_g), counted = _attention_over(monkeypatch, True, inputs, head,
                                            **op)
    close(got, want, 2e-4)
    for name in inputs:
        close(got_g[name], want_g[name], 2e-4)
    assert (counted["attention_layout.fused"]
            == counted["attention_kernel.pallas_splash"] >= 1)
    assert not counted["attention_layout.split"]
    assert not counted["attention_kernel.xla_blockwise"]


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the programs its equations call,
    a Pallas kernel's body apart: that runs on tiles in VMEM."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_the_splash_path_cuts_no_lanes_and_turns_no_float32_copy():
    """What the chip's trace shows of the GLM cell's op (PR 37), kept here
    where no chip is needed: at 20 heads of 256 with 64 turned columns the
    traced forward and backward hold no ``slice`` or ``concatenate`` that
    cuts the last axis inside a 128-lane tile, and no float32 ``[b, t, h,
    d]`` copy of an operand goes through a ``transpose``."""
    from mxnet_tpu.ops import attention

    t, h, d = 1024, 20, 256
    op = attention.CausalAttention(num_heads=h, num_kv_heads=h, head_dim=d,
                                   seq_len=t, rope_theta=1e6, rotary_dim=64)

    class Ctx:
        kept = {}

    def loss(q, k, v):
        return op.apply(Ctx(), [q, k, v], [])[0][0].astype(
            jnp.float32).sum()

    x = jax.ShapeDtypeStruct((t, h * d), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x)
    names = set()
    for eqn in _equations(jaxpr.jaxpr):
        name = eqn.primitive.name
        names.add(name)
        widths = [v.aval.shape[-1] for v in eqn.invars + eqn.outvars
                  if getattr(v.aval, "shape", ())]
        # of an operand's columns (the wrapper of JAX's kernel cuts lane 0
        # out of its log-sum-exp: not an operand, and not ours)
        if name in ("slice", "concatenate", "dynamic_slice", "pad") and (
                d in widths or h * d in widths):
            assert all(w % 128 == 0 for w in widths), eqn
        if name == "transpose":
            aval = eqn.invars[0].aval
            assert not (aval.dtype == jnp.float32
                        and sorted(aval.shape) == sorted((1, t, h, d))), eqn
    assert "pallas_call" in names


def plain_gated_experts(data, router_weight, gate_weight, up_weight,
                        down_weight, select_bias, first, top_k, scale):
    scores = jax.nn.sigmoid(data @ router_weight)
    _, eid = jax.lax.top_k(scores + select_bias, top_k)
    chosen = jnp.take_along_axis(scores, eid, axis=1)
    wts = chosen / chosen.sum(1, keepdims=True) * scale
    out = jnp.zeros_like(data)
    for j in range(up_weight.shape[0]):
        w = jnp.sum(jnp.where(eid == first + j, wts, 0.0), axis=1)
        a = jax.nn.silu(data @ gate_weight[j]) * (data @ up_weight[j])
        out = out + w[:, None] * (a @ down_weight[j])
    return out


def test_gated_experts_against_loop_under_an_imbalanced_router():
    """The gated body, forward and every gradient (rows, router, gate, up,
    down), where one held expert draws most rows and one none."""
    first, top_k, e, held, rows, h, f = 1, 2, 8, 3, 64, 12, 10
    inputs = rng_inputs(7, data=(rows, h), router_weight=(h, e),
                        gate_weight=(held, h, f), up_weight=(held, h, f),
                        down_weight=(held, f, h))
    bias = np.zeros(e, np.float32)
    bias[2], bias[3] = 5.0, -20.0       # held experts 1 (hot) and 2 (idle)
    v = {k: sym.Variable(k) for k in inputs}
    net = sym.RoutedExperts(num_experts=e, num_held=held, first_held=first,
                            top_k=top_k, scale=1.8, num_hidden=f, gated=True,
                            **v)
    assert net.list_arguments() == ["data", "router_weight", "gate_weight",
                                    "up_weight", "down_weight"]
    assert net.infer_shape(**{k: a.shape for k, a in inputs.items()})[0][2:] \
        == [(held, h, f), (held, h, f), (held, f, h)]
    telemetry.reset()
    telemetry.enable()
    try:
        against(lambda **kw: plain_gated_experts(
            select_bias=jnp.asarray(bias), first=first, top_k=top_k,
            scale=1.8, **kw), net, inputs, tol=5e-5,
            aux={"select_bias": bias})
        assert telemetry.peek("lower.experts_body.swiglu") >= 1
        assert not telemetry.peek("lower.experts_body.relu2")
    finally:
        telemetry.disable()
    from mxnet_tpu.ops import moe

    eid, _ = moe.route(jnp.asarray(inputs["data"]),
                       jnp.asarray(inputs["router_weight"]),
                       jnp.asarray(bias), top_k, 1.8)
    drew = np.bincount(np.asarray(eid).reshape(-1), minlength=e)
    assert drew[2] == rows and drew[3] == 0 and drew[1] > 0


def test_a_block_holds_an_even_share_and_a_quarter():
    """The layout's block: a balanced layer runs one block an expert. Where
    the even share fits the cap with room (the Nemotron cell: 384 of 512)
    and at toy sizes nothing moves; where it is the cap (this model's cell:
    512 of 512) the block grows to the share and a quarter."""
    from mxnet_tpu.ops import moe

    assert moe.block_rows(8192, 6, 128) == moe.block_rows(8192) == 512
    assert moe.block_rows(8192, 4, 64) == 640
    assert moe.block_rows(48, 3, 16) == moe.block_rows(48) == 8
    assert moe.block_rows(128, 2, 8) == 16
    assert moe.layout_length(8192, 4, 8, 640) % 640 == 0


def test_expert_shares_add_up_to_the_uncut_layer():
    """``model-configs`` section 4: over all 8 shares of an expert layer
    (``first_expert`` 0, 8, ..., 56 of 64 at toy widths), the routed parts
    the program computes plus the shared expert counted once equal the
    uncut reference's expert layer."""
    args = dict(TOY, layers=1, dense_layers=0, experts_total=64,
                experts_held=64, first_expert=0, top_k=4)
    params = ref.init_params(args, jax.random.PRNGKey(9))
    x = jnp.asarray(rng_inputs(9, x=(48, TOY["hidden"]))["x"])
    pre = "layer0_"
    whole, load, _ = ref.experts(params, pre, x, args)
    assert float(load.sum()) == 48 * 4
    st, mm = ref._ROUND[None]
    total = np.asarray(ref.gated(params, pre + "ffn_shared_", x, st, mm))
    inputs = {n: np.asarray(params[pre + "ffn_experts_%s_weight" % n])
              for n in ("router", "gate", "up", "down")}
    names = ["data"] + [n + "_weight" for n in inputs]
    v = {k: sym.Variable(k) for k in names}
    for first in range(0, 64, 8):
        net = sym.RoutedExperts(num_experts=64, num_held=8, first_held=first,
                                top_k=4, scale=1.8, gated=True,
                                num_hidden=TOY["expert_hidden"], **v)
        mine = {"data": np.asarray(x),
                "router_weight": inputs["router"]}
        mine.update({n + "_weight": inputs[n][first:first + 8]
                     for n in ("gate", "up", "down")})
        ex = net.bind(mx.cpu(), {k: mx.nd.array(a) for k, a in mine.items()},
                      aux_states=aux_states(
                          net, {k: a.shape for k, a in mine.items()}))
        part = ex.forward(is_train=False)[0].asnumpy()
        # the reference given the same share computes the same part
        theirs = ref.routed_part(
            x, ref.route(params, pre, x, ref.config(args)),
            tuple(jnp.asarray(mine[n + "_weight"])
                  for n in ("gate", "up", "down")), first)
        close(part, np.asarray(theirs), 5e-5)
        total = total + part
    close(total, np.asarray(whole), 5e-5)


# ---------------------------------------------------------------------------
# the model through Module.fit
# ---------------------------------------------------------------------------
COUNTERS = ("step.dispatches", "step.fused_steps", "step.fused_fallback",
            "lower.attention_kernel.xla_blockwise",
            "lower.attention_kernel.pallas_splash",
            "lower.experts_body.swiglu", "lower.experts_body.relu2",
            "lower.experts_kernel.xla_loop",
            "lower.experts_kernel.pallas_grouped",
            "moe.rows_total", "moe.rows_here", "moe.dropped_rows",
            "remat.segments", "remat.segments_recomputed",
            "remat.kept_results")


def fit_toy(monkeypatch, batches, compute_dtype=None, toy=TOY, seed=5):
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    if compute_dtype:
        monkeypatch.setenv("MXNET_COMPUTE_DTYPE", compute_dtype)
    params0 = {k: np.asarray(v) for k, v in ref.init_params(
        toy, jax.random.PRNGKey(seed)).items()}
    net = get_glm4_moe_lite(**toy)
    args_of = set(net.list_arguments())
    mod = mx.mod.Module(net, context=mx.cpu(0))
    telemetry.reset()
    telemetry.enable()
    try:
        mod.fit(Ring(batches), eval_metric="ce", optimizer="adam",
                optimizer_params=dict(RECIPE), initializer=None,
                arg_params={k: mx.nd.array(v) for k, v in params0.items()
                            if k in args_of},
                aux_params={k: mx.nd.array(v) for k, v in params0.items()
                            if k not in args_of},
                num_epoch=1)
        counters = {k: telemetry.peek(k) for k in COUNTERS}
        counters["jit_entries"] = telemetry.peek("step.fused_jit_entries",
                                                 "gauge")
    finally:
        telemetry.disable()
    return mod, params0, counters


def test_model_fits_on_the_fused_step_like_the_reference(monkeypatch):
    """Three Adam steps through ``Module.fit`` under recomputation against
    the benchmark's reference: the first gradient (Adam's first moment) and
    the three-step change by leaf, the selection biases among the leaves;
    one dispatch a step, one program; the lowerings and the experts' rows
    as telemetry reads them."""
    batches = toy_batches(3, toy=TOY)
    mod, params0, counters = fit_toy(monkeypatch, batches)
    assert mod._fused_step_active
    assert counters["step.dispatches"] == 3
    assert counters["step.fused_steps"] == 3
    assert not counters["step.fused_fallback"]
    assert counters["jit_entries"] == 1
    assert counters["lower.attention_kernel.xla_blockwise"] == TOY["layers"]
    assert not counters["lower.attention_kernel.pallas_splash"]
    assert counters["lower.experts_body.swiglu"] == 2
    assert not counters["lower.experts_body.relu2"]
    # toy widths are no whole tiles: the loop of XLA products, once a layer
    assert counters["lower.experts_kernel.xla_loop"] == 2
    assert not counters["lower.experts_kernel.pallas_grouped"]
    # (row, expert) pairs: 2 expert layers x 3 steps x 48 rows x top-2
    assert counters["moe.rows_total"] == 2 * 3 * 48 * 2
    assert 0 < counters["moe.rows_here"] < counters["moe.rows_total"]
    assert counters["moe.dropped_rows"] == 0
    assert counters["remat.segments_recomputed"] \
        == counters["remat.segments"] - 1 > 0
    # attention's result and each expert layer's result and routing
    assert counters["remat.kept_results"] >= TOY["layers"] + 2 * 2
    args, aux = mod.get_params()
    states = {k for k in params0 if k.endswith(ref.STATE)}
    assert len(states) == 2 and set(args) == set(params0) - states
    assert states <= set(aux)
    want = ref.follow(TOY, RECIPE, params0,
                      [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
                      rows=np.arange(16).reshape(2, 8))
    got = {k: v.asnumpy() for k, v in {**args, **aux}.items()
           if k in params0}
    delta = ref.leaf_norms({k: jnp.asarray(got[k] - params0[k])
                            for k in params0})
    assert set(delta) == set(want["delta_norms"])
    assert sum("experts_up_weight[" in k for k in delta) \
        == 2 * TOY["experts_held"]
    gaps = sorted(abs(float(delta[k]) - want["delta_norms"][k])
                  / max(want["delta_norms"][k], 1e-3) for k in delta)
    assert gaps[len(gaps) // 2] < 2e-4 and gaps[-1] < 1e-2, gaps[-3:]
    assert all(n > 0 for n in want["delta_norms"].values())
    # the first gradient, from Adam's first moment after ONE step from a
    # zero state: m1 = (1 - b1) (g + wd w0)
    mod, _, _ = fit_toy(monkeypatch, batches[:1])
    grads = {}
    for i, name in enumerate(mod._param_names):
        m1 = mod._updater.states[i][0].asnumpy()
        grads[name] = jnp.asarray(m1 / (1.0 - RECIPE["beta1"])
                                  - RECIPE["wd"] * params0[name])
    norms = ref.leaf_norms(grads)
    assert set(norms) == set(want["grad_norms"])
    for name, norm in norms.items():
        assert abs(float(norm) - want["grad_norms"][name]) \
            <= 2e-3 * max(want["grad_norms"][name], 1e-3), name


WHOLE_TILES = dict(TOY, hidden=128, expert_hidden=128, seq_len=64)


def test_whole_tile_experts_take_the_grouped_kernel_and_match_the_loop(
        monkeypatch):
    """A row of whole lanes and blocks of 16 rows: each gated expert layer
    takes the Pallas kernels (interpreted here), counted once a layer, and
    two Adam steps read the loop's first moments and parameters, the same
    rows counted, none dropped."""
    from mxnet_tpu.ops import moe

    batches = toy_batches(2, toy=WHOLE_TILES)
    runs = {}
    for body in ("pallas_grouped", "xla_loop"):
        if body == "xla_loop":
            monkeypatch.setattr(moe, "grouped_experts_applicable",
                                lambda *a: False)
        mod, _, counters = fit_toy(monkeypatch, batches, toy=WHOLE_TILES)
        other = "xla_loop" if body == "pallas_grouped" else "pallas_grouped"
        assert counters["lower.experts_kernel." + body] == 2
        assert not counters["lower.experts_kernel." + other]
        assert counters["lower.experts_body.swiglu"] == 2
        assert counters["moe.dropped_rows"] == 0
        assert counters["step.dispatches"] == 2
        assert not counters["step.fused_fallback"]
        params = {k: v.asnumpy() for part in mod.get_params()
                  for k, v in part.items()}
        moments = {name: mod._updater.states[i][0].asnumpy()
                   for i, name in enumerate(mod._param_names)}
        runs[body] = (counters["moe.rows_here"], params, moments)
    (rows_k, params_k, moments_k), (rows_l, params_l, moments_l) = \
        runs["pallas_grouped"], runs["xla_loop"]
    assert rows_k == rows_l > 0
    # Adam's step divides by the gradient's size: a gradient near zero
    # turns a rounding into a step, so the parameters agree less closely
    for k in params_l:
        close(params_k[k], params_l[k], 2e-4)
    for k in moments_l:
        close(moments_k[k], moments_l[k], 2e-5)
    assert any("experts_gate_weight" in k and np.abs(v).max() > 0
               for k, v in moments_k.items())


def test_model_loss_follows_the_reference(monkeypatch):
    """Step by step: the metric's mean cross-entropy after each step."""
    batches = toy_batches(3, seed=12, toy=TOY)
    losses = []

    class Watch(Ring):
        def next(self):
            if 0 < self.k < len(self.batches):
                losses.append(self.metric.get()[1] * self.k)
            return super().next()

    it = Watch(batches)
    it.metric = mx.metric.create("ce")
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    params0 = {k: np.asarray(v) for k, v in ref.init_params(
        TOY, jax.random.PRNGKey(6)).items()}
    net = get_glm4_moe_lite(**TOY)
    args_of = set(net.list_arguments())
    mod = mx.mod.Module(net, context=mx.cpu(0))
    mod.fit(it, eval_metric=it.metric, optimizer="adam",
            optimizer_params=dict(RECIPE), initializer=None,
            arg_params={k: mx.nd.array(v) for k, v in params0.items()
                        if k in args_of},
            aux_params={k: mx.nd.array(v) for k, v in params0.items()
                        if k not in args_of}, num_epoch=1)
    per_step = np.diff([0.0] + losses + [it.metric.get()[1] * 3])
    want = ref.follow(TOY, RECIPE, params0,
                      [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
                      rows=np.arange(8).reshape(2, 4))
    np.testing.assert_allclose(per_step, want["losses"], rtol=2e-4)


def test_model_trains_in_bfloat16_through_the_splash_kernel(monkeypatch):
    """At heads of whole lanes (96 + 32 = 128) the program takes the splash
    kernel (interpreted here) once a layer, in bfloat16 over float32
    masters, and every leaf stays finite and moves."""
    toy = dict(TOY, layers=2, heads=2, nope_dim=96, rope_dim=32, v_dim=128,
               seq_len=128)
    batches = toy_batches(2, batch=1, seed=13, toy=toy)
    mod, params0, counters = fit_toy(monkeypatch, batches,
                                     compute_dtype="bfloat16", toy=toy)
    assert counters["lower.attention_kernel.pallas_splash"] == 2
    assert not counters["lower.attention_kernel.xla_blockwise"]
    assert counters["step.dispatches"] == 2
    assert not counters["step.fused_fallback"]
    assert counters["jit_entries"] == 1
    args, aux = mod.get_params()
    for k, v in args.items():
        assert np.isfinite(v.asnumpy()).all(), k
        assert np.abs(v.asnumpy() - params0[k]).max() > 0, k
    # the selection biases moved by the rate, every expert's
    for k in aux:
        if k.endswith(ref.STATE):
            moved = np.abs(aux[k].asnumpy() - params0[k])
            assert moved.max() <= 2 * toy["bias_update_rate"] + 1e-6
            assert moved.max() > 0


def test_balanced_start_evens_the_seeded_router():
    """``init.balance``: the selection biases start where the balancing
    rule settles on a batch drawn from the key; with it the busiest expert
    of each layer draws near the mean, without it a multiple."""
    toy = dict(TOY, seq_len=512, experts_total=16, experts_held=4)
    key = jax.random.PRNGKey(4)
    plain = ref.init_params(toy, key)
    init = {"balance": {"from": 0.1, "to": 0.001, "steps": 200, "hold": 20,
                        "zipf_exponent": 1.0}}
    even = ref.init_params(toy, key, init)
    states = [k for k in plain if k.endswith(ref.STATE)]
    assert len(states) == 2
    for k in plain:
        if k not in states:
            np.testing.assert_array_equal(plain[k], even[k])
    ids = ref.zipf_ids(jax.random.fold_in(key, 999), toy["vocab"], 512, 1.0)
    mean = 512 * toy["top_k"] / toy["experts_total"]
    for params, worst in ((plain, None), (even, 1.15)):
        _, load, _ = ref.hidden_states(params, ids, toy, remat=False)
        most = max(float(v.max()) for v in load.values())
        if worst is None:
            assert most > 1.5 * mean
            assert all(not np.asarray(params[k]).any() for k in states)
        else:
            assert most <= worst * mean
    # anything that is no dictionary (tools/sweep_lr.py's) is no init
    again = ref.init_params(toy, key, (0.001, 0.1, 1e-4))
    np.testing.assert_array_equal(again[states[0]], plain[states[0]])


def test_reference_imports_nothing_of_the_program():
    src = open(ref.__file__).read()
    assert "import mxnet_tpu" not in src and "from mxnet_tpu" not in src
    assert "argsort" not in src and "custom_vjp" not in src


def test_bad_sizes_are_refused():
    with pytest.raises(ValueError, match="values of 8 beside keys of 12 "
                       r"\+ 4"):
        get_glm4_moe_lite(**dict(TOY, v_dim=8))
    with pytest.raises(ValueError, match="4 dense layers of 3"):
        get_glm4_moe_lite(**dict(TOY, dense_layers=4))
    with pytest.raises(ValueError, match="unknown arguments"):
        ref.config({"head_dim": 8})


def test_step_cost_counts_the_configurations_parts():
    """The reference's count at the cell's sizes: 21.76 M parameters of
    attention a layer, 698.1 M in all, the parts the readers know."""
    args = dict(layers=6, vocab=19360, experts_held=8)
    cost = ref.step_cost(args, 1, 2)
    assert round(cost["params"] / 1e6, 1) == 698.1
    per = ref.layer_cost("attention", args, 8192)
    assert per["attention_proj"][0] == 2 * 8192 * 21757952
    assert set(cost["parts"]) == {
        "attention_proj", "attention_kernel", "dense_ffn",
        "moe_grouped_matmul", "moe_rest", "lm_head_loss", "embed"}
    assert cost["flops"] == sum(f for f, _ in cost["parts"].values())
    part = ref.part_of(args)
    assert part("fwd", "FullyConnected", "layer0_kv_b") == "attention_proj"
    assert part("bwd", "CausalAttention", "layer5_attn") == "attention_kernel"
    assert part("fwd", "FullyConnected", "layer0_ffn_up") == "dense_ffn"
    assert part("fwd", "RoutedExperts", "layer3_ffn_experts") \
        == "moe_grouped_matmul"
    assert part("fwd", "FullyConnected", "layer3_ffn_shared_up") == "moe_rest"
    assert part("fwd", "RMSNorm", "layer3_ffn_norm") == "moe_rest"
    assert part("fwd", "RMSNorm", "layer3_attn_norm") == "attention_proj"
    assert part("update", "", "") == "optimizer"
    assert part("fwd", "FullyConnected", "lm_head") == "lm_head_loss"


# ---------------------------------------------------------------------------
# the two models that share the operators lower as they did
# ---------------------------------------------------------------------------
def _step_text(monkeypatch, net, params0, batches):
    """The StableHLO text of the one fused step a fit of ``net`` builds
    (locations are not printed)."""
    real_jit, lowered = jax.jit, []

    def spy(fn, **kw):
        jfn = real_jit(fn, **kw)
        if getattr(fn, "__name__", "") != "step":
            return jfn

        class Spy:
            def __call__(self, *args):
                lowered.append(jfn.lower(*args).as_text())
                return jfn(*args)

            def _cache_size(self):
                return jfn._cache_size()
        return Spy()

    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    monkeypatch.setenv("MXNET_COMPUTE_DTYPE", "bfloat16")
    monkeypatch.setattr(jax, "jit", spy)
    args_of = set(net.list_arguments())
    mod = mx.mod.Module(net, context=mx.cpu(0))
    # the matmul precision is in the text: the one tests/conftest.py sets
    with jax.default_matmul_precision("highest"):
        mod.fit(Ring(batches), eval_metric="ce", optimizer="adam",
                optimizer_params=dict(RECIPE), initializer=None,
                arg_params={k: mx.nd.array(v) for k, v in params0.items()
                            if k in args_of},
                aux_params={k: mx.nd.array(v) for k, v in params0.items()
                            if k not in args_of}, num_epoch=1)
    monkeypatch.undo()
    assert len(lowered) == 1
    return lowered[0]


# sha256 of the step's text in this container's JAX. The Olmo toy's is as PR
# 37 left it (that PR wrote ``ops.attention.rope`` over the turned columns in
# one piece and the op's XLA path around it; before it the three texts were
# those of commits 0c7ad87 (PR 31) and a1b7200 (PR 32)). The two toys with a
# ``RoutedExperts`` node are as PR 42 left them: ``moe.plan``'s layout from
# sorts and dense compares, the same arrays to the bit
# (``tests/test_moe_kernel.py``); until then they were 1228aa23... and
# c7dfb647... A PR that changes what these programs compute on purpose reads
# the new ones off this test's failure.
PARENT_TEXT = {
    "nemotron_h": "2d7da3f55448359f22199f4cd4d54782c91a0105c57def880cb408541d94be81",
    "olmo_hybrid": "01c68fef70c315c7ebbf2e1957e8e76b7ed51f9ba63815e15fdaf6d714b3330d",
    "glm4_moe_lite": "ac10154ec8d09085a5802619e71d14043909fdab3d84d22eacee0eff9b6ab543",
}


@pytest.mark.parametrize("model", sorted(PARENT_TEXT))
def test_the_models_that_share_the_operators_lower_as_they_did(monkeypatch,
                                                              model):
    """The ungated ``RoutedExperts`` and ``CausalAttention`` without
    ``rotary_dim``: the toy Nemotron and Olmo fits' fused step, bfloat16
    under recomputation, is the program text it was on the parent."""
    if model == "nemotron_h":
        toy, their, factory = NEMOTRON_TOY, nemotron_h, get_nemotron_h
    elif model == "glm4_moe_lite":
        toy, their, factory = TOY, ref, get_glm4_moe_lite
    else:
        toy, their, factory = OLMO_TOY, olmo_hybrid, get_olmo_hybrid
    params0 = {k: np.asarray(v) for k, v in their.init_params(
        toy, jax.random.PRNGKey(5)).items()}
    text = _step_text(monkeypatch, factory(**toy), params0,
                      toy_batches(1, toy=toy))
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[model]


# ---------------------------------------------------------------------------
# the toy preset's fused step, from its lowering (tests/test_hlo_gates.py)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def toy_step():
    return lower_language_toy("glm47_flash_l6_e8of64_bf16.json",
                              get_glm4_moe_lite(**TOY),
                              *toy_batches(1, toy=TOY)[0])


def test_the_toy_step_donates_every_master_moment_and_state(toy_step):
    check_state_is_donated(*toy_step)


def test_the_toy_step_takes_bfloat16_products_but_where_named(toy_step):
    check_products_are_bfloat16(*toy_step[:2], {
        # the router's scores, float32 from the normed rows (a choice of
        # experts is discontinuous: ``moe.route``): forward, recomputed,
        # and the two gradients, a layer of experts
        "RoutedExperts": 8,
        # toy widths take ``attend_blockwise``, whose backward pass takes
        # the float32 scores' cotangent against operands widened to it; the
        # cells' heads take the splash kernel (tests/test_cell_lowering.py)
        "attention": 12})
