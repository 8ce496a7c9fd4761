"""``models.get_lfm2_moe`` (gated short-convolution mixers, grouped-head
attention at 64-wide normalised heads, routed experts with no shared one
behind a leading dense layer, a head tied to the embedding) through
``Module.fit`` on the fused step against the benchmark's float32 reference;
what it forced of the operators (``GatedShortConv``, ``CausalAttention`` at
heads of half a lane tile, ``RoutedExperts(norm_eps=...)``, one parameter
with two uses) against plain ``jax.numpy``; the share by experts of
``model-configs`` section 4. Toy widths, seeded."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu import telemetry
from mxnet_tpu.models import get_lfm2_moe

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import lfm2_moe as ref  # noqa: E402
from test_hlo_gates import (check_products_are_bfloat16,  # noqa: E402
                            check_state_is_donated, lower_language_toy)
from test_glm4_moe_lite import _attention_over  # noqa: E402
from test_nemotron_h import (Ring, against, aux_states, close,  # noqa: E402
                             rng_inputs, run_op)
from test_olmo_hybrid import toy_batches  # noqa: E402

TOY = dict(layer_types=["conv", "full_attention", "conv", "conv"],
           dense_layers=1, hidden=32, vocab=96, heads=4, kv_heads=2,
           head_dim=8, dense_hidden=48, experts_total=8, experts_held=4,
           first_expert=2, top_k=2, expert_hidden=24, seq_len=24,
           bias_update_rate=0.01)
RECIPE = {"learning_rate": 0.001, "wd": 0.01, "beta1": 0.9, "beta2": 0.95,
          "epsilon": 1e-8, "rescale_grad": 1.0}


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------
def shifted_products(data, weight, t):
    """``C * conv(B * z)`` position by position: three shifted products a
    sequence, nothing read from before its start."""
    rows, c = data.shape[0], data.shape[1] // 3
    b, gate, z = (data[:, i * c:(i + 1) * c].reshape(rows // t, t, c)
                  for i in range(3))
    v = b * z
    taps = weight.shape[1]
    out = jnp.zeros_like(v)
    for k in range(taps):
        back = taps - 1 - k
        out = out.at[:, back:].add(v[:, :t - back] * weight[:, k])
    return (gate * out).reshape(rows, c)


@pytest.mark.parametrize("batch,t,kernel", [(1, 16, 3), (2, 12, 3),
                                            (3, 8, 4)],
                         ids=["one-sequence", "two-sequences", "four-taps"])
def test_gated_short_conv_against_shifted_products(batch, t, kernel):
    """Forward and both gradients; a sequence's first positions read zeros
    and nothing of the sequence before it in the batch."""
    c = 10
    inputs = rng_inputs(3, data=(batch * t, 3 * c), weight=(c, kernel))
    v = {k: sym.Variable(k) for k in inputs}
    net = sym.GatedShortConv(kernel=kernel, seq_len=t, **v)
    assert net.list_arguments() == ["data", "weight"]
    assert net.infer_shape(data=(batch * t, 3 * c))[:2] == (
        [(batch * t, 3 * c), (c, kernel)], [(batch * t, c)])
    telemetry.reset()
    telemetry.enable()
    try:
        against(lambda **kw: shifted_products(t=t, **kw), net, inputs)
        assert telemetry.peek("lower.shortconv_body.xla_fused") >= 1
    finally:
        telemetry.disable()
    # the first position of every sequence is its own gates and last tap
    out = run_op(net, inputs, np.ones((batch * t, c), np.float32))[0]
    d, w = inputs["data"], inputs["weight"]
    for s in range(batch):
        row = d[s * t]
        np.testing.assert_allclose(
            out[s * t], row[c:2 * c] * row[:c] * row[2 * c:] * w[:, -1],
            rtol=1e-5, atol=1e-6)
    # and it is the reference's own form
    close(out, np.asarray(ref.gated_conv(jnp.asarray(d), jnp.asarray(w), t)))


def test_gated_short_conv_refuses_bad_shapes():
    net = sym.GatedShortConv(data=sym.Variable("data"), kernel=3, seq_len=8)
    with pytest.raises(mx.MXNetError, match="three equal chunks"):
        net.infer_shape(data=(16, 10))
    with pytest.raises(mx.MXNetError, match="whole sequences"):
        net.infer_shape(data=(12, 9))


# ---------------------------------------------------------------------------
# 64-wide heads
# ---------------------------------------------------------------------------
def plain_lfm2_attention(query, key, value, qgamma, kgamma, t, hq, hkv, d,
                         theta, eps):
    """Every head's RMSNorm under one gamma, rotary over the whole head
    written out position by position, dense causal softmax a head."""
    q = query.reshape(t, hq, d)
    k, v = key.reshape(t, hkv, d), value.reshape(t, hkv, d)

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g

    half = d // 2
    ang = np.arange(t)[:, None] * theta ** (-np.arange(half) / half)[None]
    cos, sin = (jnp.asarray(f(ang), jnp.float32)[:, None, :]
                for f in (np.cos, np.sin))

    def turn(x):
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    q, k = turn(norm(q, qgamma)), turn(norm(k, kgamma))
    k, v = (jnp.repeat(x, hq // hkv, axis=1) for x in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    s = jnp.where(np.tril(np.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v).reshape(
        t, hq * d)


def test_64_wide_normalised_heads_through_the_kernel():
    """The model's attention as the graph builds it (a head's norm under a
    shared gamma, then ``CausalAttention`` with rotary) at 4 query heads of
    64 over 2 key/value heads: through the splash kernel and the pass that
    moves two heads a tile (interpreted here), forward and every gradient,
    against the plain form."""
    t, hq, hkv, d = 256, 4, 2, 64
    inputs = rng_inputs(5, query=(t, hq * d), key=(t, hkv * d),
                        value=(t, hkv * d), qgamma=(d,), kgamma=(d,))
    v = {k: sym.Variable(k) for k in inputs}
    net = sym.CausalAttention(
        query=sym.RMSNorm(data=v["query"], gamma=v["qgamma"], num_groups=hq,
                          shared_gamma=True, eps=1e-5),
        key=sym.RMSNorm(data=v["key"], gamma=v["kgamma"], num_groups=hkv,
                        shared_gamma=True, eps=1e-5),
        value=v["value"], num_heads=hq, num_kv_heads=hkv, head_dim=d,
        seq_len=t, rope_theta=1e6)
    telemetry.reset()
    telemetry.enable()
    try:
        against(lambda **kw: plain_lfm2_attention(
            t=t, hq=hq, hkv=hkv, d=d, theta=1e6, eps=1e-5, **kw), net,
            inputs, tol=2e-4)
        assert telemetry.peek("lower.attention_kernel.pallas_splash") >= 1
        assert not telemetry.peek("lower.attention_kernel.xla_blockwise")
    finally:
        telemetry.disable()


@pytest.mark.parametrize("heads,kv_heads,rotary", [
    (8, 2, dict(rope_theta=1e6)), (2, 2, dict(rotary_dim=16)),
    (4, 4, dict(rotary=False))],
    ids=["lfm2", "part-of-the-head", "no-rotary"])
def test_64_wide_heads_agree_with_attend_blockwise(monkeypatch, heads,
                                                   kv_heads, rotary):
    """64-wide heads at 512 positions: operands turned and laid out two
    heads a grid step and attended by the splash kernel (all interpreted
    here) against the XLA lowering (``attend_blockwise``): the output and
    the three input gradients."""
    t, d = 512, 64
    inputs = rng_inputs(7, query=(t, heads * d), key=(t, kv_heads * d),
                        value=(t, kv_heads * d))
    head = rng_inputs(8, h=(t, heads * d))["h"]
    op = dict(num_heads=heads, num_kv_heads=kv_heads, head_dim=d, seq_len=t,
              **rotary)
    (want, want_g), counted = _attention_over(monkeypatch, False, inputs,
                                              head, **op)
    assert counted["attention_kernel.xla_blockwise"] >= 1
    (got, got_g), counted = _attention_over(monkeypatch, True, inputs, head,
                                            **op)
    close(got, want, 2e-4)
    for name in inputs:
        close(got_g[name], want_g[name], 2e-4)
    assert (counted["attention_layout.fused"]
            == counted["attention_kernel.pallas_splash"] >= 1)
    assert not counted["attention_kernel.xla_blockwise"]


@pytest.mark.parametrize("batch,t,heads,turned,dtype", [
    (1, 256, 8, 64, "bfloat16"), (2, 128, 2, 64, "float32"),
    (1, 128, 4, 0, "bfloat16"), (1, 128, 2, 16, "float32")],
    ids=["lfm2", "two-sequences", "plain", "part-of-the-head"])
def test_the_pass_moves_two_64_wide_heads_a_tile(batch, t, heads, turned,
                                                 dtype):
    """``attention_relayout`` at heads of half a lane tile against ``rope``
    with the scale folded in and a transpose, and its backward pass against
    theirs: a lane's partner is half the turned width away WITHIN its own
    head, never in the head beside it."""
    from mxnet_tpu.ops import attention

    d, half, scale = 64, turned // 2, 0.125
    tables = attention.relayout_tables(t, 1e6, half, d)
    shape = (batch, t, heads, d)
    x = jnp.asarray(rng_inputs(3, x=(batch * t, heads * d))["x"], dtype)
    g = jnp.asarray(rng_inputs(4, g=(batch, heads, t, d))["g"], dtype)
    got, back = jax.vjp(lambda x: attention._relaid(
        x, tables, batch=batch, heads=heads, half=half, scale=scale), x)
    want, want_back = jax.vjp(lambda x: attention.rope(
        x.reshape(shape), 1e6, scale, turned, pos_axis=1).transpose(
            0, 2, 1, 3) if half else (x.reshape(shape).astype(
                jnp.float32) * scale).astype(dtype).transpose(0, 2, 1, 3), x)
    tol = 2 ** -7 if dtype == "bfloat16" else 1e-6
    for a, b in ((got, want), (back(g)[0], want_back(g)[0])):
        assert a.dtype == b.dtype == x.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=tol,
                                   atol=tol * 1e-2)
    there = attention._relaid(x, batch=batch, heads=heads)
    np.testing.assert_array_equal(
        np.asarray(attention._relaid(there, back=True, batch=batch,
                                     heads=heads), np.float32),
        np.asarray(x, np.float32))


def test_which_heads_take_the_kernel():
    from mxnet_tpu.ops.attention import CausalAttention

    def applies(**kw):
        return CausalAttention(**dict(dict(
            num_heads=32, num_kv_heads=8, head_dim=64, seq_len=8192),
            **kw))._splash_applies()

    assert applies() and applies(head_dim=128) and applies(head_dim=256)
    assert not applies(num_heads=3, num_kv_heads=3)     # a head with no pair
    assert not applies(head_dim=32) and not applies(head_dim=96)
    assert not applies(seq_len=8192 + 64)


# ---------------------------------------------------------------------------
# the experts: no shared one, the normalisation's epsilon, the share
# ---------------------------------------------------------------------------
def test_the_normalisations_epsilon_is_the_ops_parameter():
    """``norm_eps`` beside the chosen scores' sum: 1e-20 by default (the
    two expert cells' programs), the family's 1e-6 where the model asks."""
    from mxnet_tpu.ops import moe

    noise = rng_inputs(2, x=(16, 12), router=(12, 8))
    # scores near 1e-6 (a logit of -14), so that the epsilon shows
    x = jnp.asarray(1.0 + 0.01 * noise["x"])
    router = jnp.asarray(-14.0 / 12 + 0.01 * noise["router"])
    bias = jnp.zeros((8,))
    eid, dflt = moe.route(x, router, bias, 2, 1.0)
    eid6, six = moe.route(x, router, bias, 2, 1.0, norm_eps=1e-6)
    np.testing.assert_array_equal(eid, eid6)
    scores = jax.nn.sigmoid(x @ router)
    chosen = jnp.take_along_axis(scores, eid, axis=1)
    close(six, chosen / (chosen.sum(1, keepdims=True) + 1e-6))
    close(dflt, chosen / (chosen.sum(1, keepdims=True) + 1e-20))
    assert float(jnp.abs(six - dflt).max()) > 1e-3
    net = get_lfm2_moe(**TOY)
    ops = [n.op for n in net._topo() if n.op is not None
           and type(n.op).__name__ == "RoutedExperts"]
    assert len(ops) == 3 and all(o.norm_eps == 1e-6 for o in ops)
    assert moe.RoutedExperts(num_experts=8, num_held=2, top_k=2,
                             num_hidden=4).norm_eps == 1e-20


def test_expert_shares_add_up_to_the_uncut_layer():
    """``model-configs`` section 4: over all 8 shares of an expert layer
    (``first_expert`` 0, 8, ..., 56 of 64 at toy widths) the parts the
    program computes add up to the uncut reference's expert layer; there is
    no shared expert, so nothing is counted twice and nothing beside."""
    args = dict(TOY, layer_types=["conv"], dense_layers=0, experts_total=64,
                experts_held=64, first_expert=0, top_k=4)
    params = ref.init_params(args, jax.random.PRNGKey(9))
    x = jnp.asarray(rng_inputs(9, x=(48, TOY["hidden"]))["x"])
    pre = "layer0_"
    whole, load, _ = ref.experts(params, pre, x, args)
    assert float(load.sum()) == 48 * 4
    inputs = {n: np.asarray(params[pre + "ffn_experts_%s_weight" % n])
              for n in ("router", "gate", "up", "down")}
    names = ["data"] + [n + "_weight" for n in inputs]
    v = {k: sym.Variable(k) for k in names}
    total = 0.0
    for first in range(0, 64, 8):
        net = sym.RoutedExperts(num_experts=64, num_held=8, first_held=first,
                                top_k=4, scale=1.0, gated=True, norm_eps=1e-6,
                                num_hidden=TOY["expert_hidden"], **v)
        mine = {"data": np.asarray(x), "router_weight": inputs["router"]}
        mine.update({n + "_weight": inputs[n][first:first + 8]
                     for n in ("gate", "up", "down")})
        ex = net.bind(mx.cpu(), {k: mx.nd.array(a) for k, a in mine.items()},
                      aux_states=aux_states(
                          net, {k: a.shape for k, a in mine.items()}))
        part = ex.forward(is_train=False)[0].asnumpy()
        # the reference given the same share computes the same part
        share = dict(args, experts_held=8, first_expert=first)
        theirs, _, _ = ref.experts(
            {**params, **{pre + "ffn_experts_%s_weight" % n:
                          jnp.asarray(mine[n + "_weight"])
                          for n in ("gate", "up", "down")}}, pre, x, share)
        close(part, np.asarray(theirs), 5e-5)
        total = total + part
    close(total, np.asarray(whole), 5e-5)


# ---------------------------------------------------------------------------
# the tied head
# ---------------------------------------------------------------------------
def _head_net(tied):
    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    table = sym.Variable("embed_weight")
    x = sym.Embedding(data=data, weight=table, input_dim=20, output_dim=8,
                      name="embed")
    x = sym.FullyConnected(data=sym.Reshape(data=x, shape=(-1, 8)),
                           num_hidden=8, no_bias=True, name="mid")
    head = dict(weight=table) if tied else {}
    logits = sym.FullyConnected(data=x, num_hidden=20, no_bias=True,
                                name="lm_head", **head)
    return sym.SoftmaxOutput(data=logits,
                             label=sym.Reshape(data=label, shape=(-1,)),
                             normalization="valid", name="softmax")


def test_a_tied_heads_gradient_is_the_sum_of_its_two_uses():
    """One ``Variable`` read by ``Embedding`` and by the head: one argument,
    and its gradient the embedding's and the head's, taken apart in the
    untied twin at the same values, added up."""
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 20, (2, 6)).astype(np.int32)
    labels = rng.integers(0, 20, (2, 6)).astype(np.int32)
    w = rng_inputs(4, embed_weight=(20, 8), mid_weight=(8, 8))
    grads = {}
    for tied in (True, False):
        net = _head_net(tied)
        mine = dict(w) if tied else dict(w, lm_head_weight=w["embed_weight"])
        assert ("lm_head_weight" in net.list_arguments()) is (not tied)
        assert net.list_arguments().count("embed_weight") == 1
        args = {k: mx.nd.array(v) for k, v in mine.items()}
        args["data"] = mx.nd.array(ids, dtype=np.int32)
        args["softmax_label"] = mx.nd.array(labels, dtype=np.int32)
        g = {k: mx.nd.zeros(v.shape) for k, v in mine.items()}
        ex = net.bind(mx.cpu(), args, args_grad=g)
        ex.forward(is_train=True)
        ex.backward()
        grads[tied] = {k: a.asnumpy() for k, a in g.items()}
    apart = grads[False]
    assert np.abs(apart["embed_weight"]).max() > 0
    assert np.abs(apart["lm_head_weight"]).max() > 0
    close(grads[True]["embed_weight"],
          apart["embed_weight"] + apart["lm_head_weight"])
    close(grads[True]["mid_weight"], apart["mid_weight"])


# ---------------------------------------------------------------------------
# the model through Module.fit
# ---------------------------------------------------------------------------
COUNTERS = ("step.dispatches", "step.fused_steps", "step.fused_fallback",
            "lower.shortconv_body.xla_fused",
            "lower.attention_kernel.xla_blockwise",
            "lower.attention_kernel.pallas_splash",
            "lower.experts_body.swiglu", "lower.experts_kernel.xla_loop",
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
    net = get_lfm2_moe(**toy)
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
    the three-step change by leaf, the selection biases among the leaves and
    the tied matrix ONE leaf with one master; one dispatch a step, one
    program; the lowerings and the experts' rows as telemetry reads them.
    Tolerances: float32 on both sides, so what is left is the order of
    sums (2e-4 of a leaf's change at the median, 1e-2 at the worst leaf,
    where Adam divides a rounding by a gradient near zero; 2e-3 of a
    leaf's first gradient)."""
    batches = toy_batches(3, toy=TOY)
    mod, params0, counters = fit_toy(monkeypatch, batches)
    assert mod._fused_step_active
    assert counters["step.dispatches"] == 3
    assert counters["step.fused_steps"] == 3
    assert not counters["step.fused_fallback"]
    assert counters["jit_entries"] == 1
    assert counters["lower.shortconv_body.xla_fused"] == 3
    assert counters["lower.attention_kernel.xla_blockwise"] == 1
    assert not counters["lower.attention_kernel.pallas_splash"]
    assert counters["lower.experts_body.swiglu"] == 3
    assert counters["lower.experts_kernel.xla_loop"] == 3
    # (row, expert) pairs: 3 expert layers x 3 steps x 48 rows x top-2
    assert counters["moe.rows_total"] == 3 * 3 * 48 * 2
    assert 0 < counters["moe.rows_here"] < counters["moe.rows_total"]
    assert counters["moe.dropped_rows"] == 0
    assert counters["remat.segments_recomputed"] \
        == counters["remat.segments"] - 1 > 0
    args, aux = mod.get_params()
    states = {k for k in params0 if k.endswith(ref.STATE)}
    assert len(states) == 3 and set(args) == set(params0) - states
    assert states <= set(aux)
    # one parameter, one master and one pair of moments for the tied matrix
    assert "lm_head_weight" not in args
    assert mod._param_names.count("embed_weight") == 1
    assert len(mod._updater.states) == len(mod._param_names)
    want = ref.follow(TOY, RECIPE, params0,
                      [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
                      rows=np.arange(16).reshape(2, 8))
    got = {k: v.asnumpy() for k, v in {**args, **aux}.items()
           if k in params0}
    delta = ref.leaf_norms({k: jnp.asarray(got[k] - params0[k])
                            for k in params0})
    assert set(delta) == set(want["delta_norms"])
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


def test_forward_and_loss_sit_on_the_reference(monkeypatch):
    """One bound forward pass of the program at seeded weights: the
    probabilities the head puts out against the reference's
    log-probabilities (float32 on both sides: 2e-4 of the largest), and the
    mean cross-entropy against its loss (1e-5 relative)."""
    (ids, labels), = toy_batches(1, seed=12, toy=TOY)
    params0 = {k: np.asarray(v) for k, v in ref.init_params(
        TOY, jax.random.PRNGKey(6)).items()}
    net = get_lfm2_moe(**TOY)
    args_of = set(net.list_arguments())
    args = {k: mx.nd.array(v) for k, v in params0.items() if k in args_of}
    args["data"] = mx.nd.array(ids, dtype=np.int32)
    args["softmax_label"] = mx.nd.array(labels, dtype=np.int32)
    given = {k: v for k, v in params0.items() if k not in args_of}
    ex = net.bind(mx.cpu(), args, aux_states=aux_states(
        net, {"data": ids.shape, "softmax_label": labels.shape}, given))
    prob = ex.forward(is_train=False)[0].asnumpy()
    rows = np.arange(prob.shape[0])
    loss, logp = ref.loss_and_logprob(
        {k: jnp.asarray(v) for k, v in params0.items()}, jnp.asarray(ids),
        jnp.asarray(labels), TOY, jnp.asarray(rows), remat=False)
    close(np.log(prob), np.asarray(logp), 2e-4)
    mine = -np.log(prob[rows, labels.reshape(-1)]).mean()
    assert abs(mine - float(loss)) <= 1e-5 * float(loss)


def test_model_trains_in_bfloat16_through_the_kernels(monkeypatch):
    """At the published head width (64) and a sequence of whole blocks the
    program takes the splash kernel (interpreted here) once an attention
    layer, in bfloat16 over float32 masters, and every leaf stays finite
    and moves, the selection biases by the rate."""
    toy = dict(TOY, layer_types=["conv", "full_attention", "conv"],
               heads=4, kv_heads=2, head_dim=64, seq_len=128)
    batches = toy_batches(2, batch=1, seed=13, toy=toy)
    mod, params0, counters = fit_toy(monkeypatch, batches,
                                     compute_dtype="bfloat16", toy=toy)
    assert counters["lower.attention_kernel.pallas_splash"] == 1
    assert not counters["lower.attention_kernel.xla_blockwise"]
    assert counters["lower.shortconv_body.xla_fused"] == 2
    assert counters["step.dispatches"] == 2
    assert not counters["step.fused_fallback"]
    assert counters["jit_entries"] == 1
    args, aux = mod.get_params()
    for k, v in args.items():
        assert np.isfinite(v.asnumpy()).all(), k
        assert np.abs(v.asnumpy() - params0[k]).max() > 0, k
    for k in aux:
        if k.endswith(ref.STATE):
            moved = np.abs(aux[k].asnumpy() - params0[k])
            assert 0 < moved.max() <= 2 * toy["bias_update_rate"] + 1e-6


def test_an_untied_head_is_a_parameter_of_its_own():
    net = get_lfm2_moe(**dict(TOY, tie_head=False))
    assert "lm_head_weight" in net.list_arguments()
    shapes = ref.param_shapes(dict(TOY, tie_head=False))
    assert shapes["lm_head_weight"] == shapes["embed_weight"]
    assert "lm_head_weight" not in ref.param_shapes(TOY)
    assert set(ref.param_shapes(TOY, states=False)) \
        == set(get_lfm2_moe(**TOY).list_arguments()) - {"data",
                                                         "softmax_label"}


def test_reference_imports_nothing_of_the_program():
    src = open(ref.__file__).read()
    assert "import mxnet_tpu" not in src and "from mxnet_tpu" not in src
    assert "argsort" not in src and "custom_vjp" not in src


def test_bad_sizes_are_refused():
    with pytest.raises(ValueError, match="5 dense layers of 4"):
        get_lfm2_moe(**dict(TOY, dense_layers=5))
    with pytest.raises(ValueError, match="layer 1 is 'linear'"):
        get_lfm2_moe(**dict(TOY, layer_types=["conv", "linear"]))
    with pytest.raises(ValueError, match="unknown arguments"):
        ref.config({"q_rank": 8})
    with pytest.raises(ValueError, match="layer 0 is 'mamba'"):
        ref.config({"layer_types": ["mamba"]})


def test_published_layer_types_are_the_models():
    """30 conv and 10 attention layers, attention at 2, 6, ... 38; the
    benchmark's cut is published layers 1-9."""
    from mxnet_tpu.models import lfm2_moe

    kinds = lfm2_moe.LAYER_TYPES
    assert kinds == ref.LAYER_TYPES and len(kinds) == 40
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] \
        == list(range(2, 40, 4))
    assert list(kinds[1:10]) == ["conv", "full_attention", "conv", "conv",
                                 "conv", "full_attention", "conv", "conv",
                                 "conv"]


def test_step_cost_counts_the_configurations_parts():
    """The reference's count at the cell's sizes: 16.78 M parameters a conv
    mixer, 832.6 M in all with the tied matrix once, attention at the
    model's own 64 columns a head, the parts the readers know."""
    args = dict(layer_types=list(ref.LAYER_TYPES[1:10]), dense_layers=1,
                vocab=8192, experts_held=8)
    cost = ref.step_cost(args, 1, 2)
    # ISSUE 38's table (832.6 M) and the 19 norms' 38,912 weights
    assert cost["params"] == 832651520
    per = ref.layer_cost("conv", args, 8192)
    assert per["shortconv"][0] == 2 * 8192 * 16783360 + 2 * 8192 * 2048
    attn = ref.layer_cost("full_attention", args, 8192)
    assert attn["attention_kernel"][0] == 8192 * 8192 * 32 * 2 * 64
    assert attn["attention_proj"][0] == 2 * 8192 * (
        2 * 2048 * 2048 + 2 * 2048 * 512)
    assert set(cost["parts"]) == {
        "shortconv", "attention_proj", "attention_kernel", "dense_ffn",
        "moe_grouped_matmul", "moe_rest", "lm_head_loss", "embed"}
    assert cost["flops"] == sum(f for f, _ in cost["parts"].values())
    part = ref.part_of(args)
    assert part("fwd", "FullyConnected", "layer0_conv_in") == "shortconv"
    assert part("bwd", "GatedShortConv", "layer2_conv") == "shortconv"
    assert part("fwd", "RMSNorm", "layer0_operator_norm") == "shortconv"
    assert part("fwd", "RMSNorm", "layer1_operator_norm") == "attention_proj"
    assert part("fwd", "RMSNorm", "layer1_qnorm") == "attention_proj"
    assert part("bwd", "CausalAttention", "layer5_attn") == "attention_kernel"
    assert part("fwd", "FullyConnected", "layer0_ffn_up") == "dense_ffn"
    assert part("fwd", "RoutedExperts", "layer3_ffn_experts") \
        == "moe_grouped_matmul"
    assert part("fwd", "RMSNorm", "layer3_ffn_norm") == "moe_rest"
    assert part("update", "", "") == "optimizer"
    assert part("fwd", "FullyConnected", "lm_head") == "lm_head_loss"
    assert part("fwd", "Embedding", "embed") == "other:Embedding"


# ---------------------------------------------------------------------------
# the toy preset's fused step, from its lowering (tests/test_hlo_gates.py)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def toy_step():
    return lower_language_toy("lfm2_24b_a2b_e8of64_bf16.json",
                              get_lfm2_moe(**TOY),
                              *toy_batches(1, toy=TOY)[0])


def test_the_toy_step_donates_every_master_moment_and_state(toy_step):
    check_state_is_donated(*toy_step)


def test_the_toy_step_takes_bfloat16_products_but_where_named(toy_step):
    check_products_are_bfloat16(*toy_step[:2], {
        # the router's scores, float32 from the normed rows (a choice of
        # experts is discontinuous: ``moe.route``): forward, recomputed,
        # and the two gradients, a layer of experts
        "RoutedExperts": 11,
        # toy widths take ``attend_blockwise``, whose backward pass takes
        # the float32 scores' cotangent against operands widened to it; the
        # cells' heads take the splash kernel (tests/test_cell_lowering.py)
        "attention": 4})
