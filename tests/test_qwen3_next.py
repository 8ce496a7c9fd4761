"""``models.get_qwen3_next`` (Qwen3-Next: the gated delta rule with two value
heads a key head three layers in four, attention with an output gate and
rotary over a quarter of a 256-wide head, 512 small experts chosen 10 a
token by a softmax router with an auxiliary load-balancing loss beside a
gated shared expert) through ``Module.fit`` on the fused step against the
benchmark's float32 reference; the operators' new arguments
(``GatedDeltaRule(num_key_heads=...)``, ``RoutedExperts(score_func=
"softmax", aux_loss_coef=...)``) against plain ``jax.numpy`` and, under
their defaults, against the parent's traced programs; and the share by
experts of ``model-configs`` section 4. Toy widths, seeded."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu import telemetry
from mxnet_tpu.models import get_qwen3_next
from mxnet_tpu.ops import moe

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import qwen3_next as ref  # noqa: E402
from op_program_text import program_hashes  # noqa: E402
from test_hlo_gates import (check_products_are_bfloat16,  # noqa: E402
                            check_state_is_donated, lower_language_toy)
from test_nemotron_h import (Ring, against, aux_states, close,  # noqa: E402
                             rng_inputs, run_op)
from test_olmo_hybrid import toy_batches  # noqa: E402

CONFIG = "qwen3_next_l4_e32of512_bf16.json"
TOY = dict(layer_types=["linear_attention", "linear_attention",
                        "full_attention", "linear_attention"],
           hidden=32, vocab=96, heads=4, kv_heads=2, head_dim=8, rotary_dim=4,
           linear_key_heads=2, linear_value_heads=4, linear_key_dim=8,
           linear_value_dim=8, experts_total=32, experts_held=8,
           first_expert=8, top_k=4, expert_hidden=16, shared_hidden=16,
           aux_loss_coef=0.1, seq_len=64, chunk=32)
RECIPE = {"learning_rate": 0.001, "wd": 0.0, "beta1": 0.9, "beta2": 0.95,
          "epsilon": 1e-8, "rescale_grad": 1.0}


# ---------------------------------------------------------------------------
# the delta rule with two value heads a key head
# ---------------------------------------------------------------------------
def plain_delta(query, key, value, a, b, A_log, dt_bias, t, hk, hv, dk, dv):
    """The op's statement written out: q and k normalised a KEY head, value
    head j on key head ``j // (hv / hk)`` with its own decay and step gate,
    the recurrence a position at a time."""
    def unit(x):
        x = x.reshape(-1, t, hk, dk)
        x = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        return jnp.repeat(x, hv // hk, axis=2)

    g = (-jnp.exp(A_log) * jax.nn.softplus(a + dt_bias)).reshape(-1, t, hv)
    o = ref.delta_rule(unit(query) * dk ** -0.5, unit(key),
                       value.reshape(-1, t, hv, dv), g[..., None],
                       jax.nn.sigmoid(b).reshape(-1, t, hv), t)
    return o.reshape(-1, hv * dv)


@pytest.mark.parametrize("dk,dv,ratio,body", [
    (6, 10, 2, "xla_chunked"), (8, 16, 2, "pallas_chunked"),
    (8, 8, 4, "pallas_chunked")],
    ids=["xla_body", "pallas_kernel", "four_a_key_head"])
def test_grouped_key_heads_against_the_recurrence(dk, dv, ratio, body):
    """``num_key_heads`` under ``num_heads`` value heads against the
    position-wise recurrence over two sequences: the output and every
    input's gradient (``dq``, ``dk`` the sums over the value heads that
    share the key head: autodiff's through the repeat), on the XLA body and
    on the Pallas chunk kernels (interpreted), each counted once with the
    grouping."""
    t, hk = 64, 2
    hv = hk * ratio
    inputs = rng_inputs(3, query=(2 * t, hk * dk), key=(2 * t, hk * dk),
                        value=(2 * t, hv * dv), a=(2 * t, hv), b=(2 * t, hv),
                        A_log=(hv,), dt_bias=(hv,))
    net = sym.GatedDeltaRule(num_heads=hv, num_key_heads=hk, key_dim=dk,
                             value_dim=dv, chunk=32, seq_len=t,
                             **{k: sym.Variable(k) for k in inputs})
    assert net.infer_shape(**{k: v.shape for k, v in inputs.items()})[1] \
        == [(2 * t, hv * dv)]
    telemetry.reset()
    telemetry.enable()
    try:
        against(lambda **kw: plain_delta(t=t, hk=hk, hv=hv, dk=dk, dv=dv,
                                         **kw), net, inputs, tol=5e-5)
        counted = {k: telemetry.peek("lower." + k) or 0 for k in (
            "delta_rule_heads.grouped", "delta_rule_heads.equal",
            "delta_rule_kernel.pallas_chunked",
            "delta_rule_kernel.xla_chunked")}
    finally:
        telemetry.disable()
    assert counted["delta_rule_heads.grouped"] >= 1
    assert not counted["delta_rule_heads.equal"]
    assert counted["delta_rule_kernel." + body] >= 1
    other = {"xla_chunked", "pallas_chunked"} - {body}
    assert not counted["delta_rule_kernel." + other.pop()]


def test_as_many_key_heads_as_value_heads_is_the_default():
    """``num_key_heads = num_heads`` says what leaving it out says: the
    same traced program, counted ``equal``."""
    t, h, dk, dv = 64, 2, 8, 8
    inputs = rng_inputs(4, query=(t, h * dk), key=(t, h * dk),
                        value=(t, h * dv), a=(t, h), b=(t, h), A_log=(h,),
                        dt_bias=(h,))
    head = rng_inputs(5, o=(t, h * dv))["o"]
    op = dict(num_heads=h, key_dim=dk, value_dim=dv, chunk=32, seq_len=t)
    v = {k: sym.Variable(k) for k in inputs}
    telemetry.reset()
    telemetry.enable()
    try:
        want, want_g = run_op(sym.GatedDeltaRule(**op, **v), inputs, head)
        got, got_g = run_op(sym.GatedDeltaRule(num_key_heads=h, **op, **v),
                            inputs, head)
        assert telemetry.peek("lower.delta_rule_heads.equal") >= 2
        assert not telemetry.peek("lower.delta_rule_heads.grouped")
    finally:
        telemetry.disable()
    assert np.array_equal(np.asarray(got), np.asarray(want))
    for name in inputs:
        assert np.array_equal(np.asarray(got_g[name]),
                              np.asarray(want_g[name]))


def test_bad_key_heads_are_refused():
    v = {k: sym.Variable(k) for k in ("query", "key", "value", "a", "b")}
    op = dict(num_heads=4, key_dim=8, value_dim=8, seq_len=32)
    with pytest.raises(mx.base.MXNetError, match="evenly"):
        sym.GatedDeltaRule(num_key_heads=3, **op, **v).infer_shape(
            query=(32, 24))
    with pytest.raises(mx.base.MXNetError, match="2 heads"):
        sym.GatedDeltaRule(num_key_heads=2, **op, **v).infer_shape(
            query=(32, 32))
    shapes = sym.GatedDeltaRule(num_key_heads=2, **op, **v).infer_shape(
        query=(32, 16))
    assert shapes[0][:5] == [(32, 16), (32, 16), (32, 32), (32, 4), (32, 4)]


# sha256 of the operators' traced programs at the older cells' shapes, as
# ``python tests/op_program_text.py`` printed them on the parent of the PR
# that added the arguments (commit bdd296d). A PR that changes what these
# nodes compute on purpose reads the new ones off this test's failure:
# ``ling.delta`` is PR 45's (the kernels read the node's rows where they lie,
# ``lower.delta_rule_layout.rows``); ``olmo.delta``, heads of 96 x 192, keeps
# the head-major entry and the text of bdd296d.
PARENT_PROGRAMS = {
    "glm.experts": "54bf1fac1c4a93f5be2dd88bee0bff4d8f7a00a15f7032092a9338e13a941aff",
    "lfm2.experts": "e80ff383d43456a3dc0ff0c51bf9e2d05dbda7a77d2c689944ef552d94bb30a2",
    "ling.delta": "e3130547eb3872b48f798501af196d1738fc989f7f8137d7b3ea52853791794a",
    "ling.experts": "ced652c76c75dc37b0baca02e22814fad6f364adb3490af0acdd07d3bde6ded9",
    "nemotron.experts": "a3dbd7ae9d8c11d1c07eebe0614a5865db811e466afa5ebf8b3778972f2b4158",
    "olmo.delta": "e3af893438200de39a473a75fc465b74578f0dd4ac449cefd7281c05722f0a6c",
}


@pytest.fixture(scope="module")
def traced_programs():
    return program_hashes(sorted(PARENT_PROGRAMS))


@pytest.mark.parametrize("node", sorted(PARENT_PROGRAMS))
def test_the_older_cells_nodes_trace_the_parents_program(traced_programs,
                                                         node):
    """Under the new arguments' defaults (as many key heads as value heads;
    sigmoid scores, no auxiliary loss) the Olmo and Ling cells'
    ``GatedDeltaRule`` and the four older expert cells' ``RoutedExperts``,
    at their published shapes and 8,192 positions, trace the value and the
    gradients the parent traced, to the character (the Ling cell's delta
    rule: the text PR 45 gave it, see above)."""
    assert traced_programs[node] == PARENT_PROGRAMS[node]


# ---------------------------------------------------------------------------
# the softmax router and its auxiliary loss
# ---------------------------------------------------------------------------
def test_softmax_scores_against_a_written_out_router():
    """``route(score_func="softmax")`` over 32 experts, 4 a row: the ids are
    the largest probabilities' (and the largest logits'), the weights the
    chosen probabilities over their sum; the sigmoid default chooses the
    same experts (one monotone function for the other) at other weights."""
    rng = np.random.default_rng(3)
    s, e, h = 96, 32, 16
    x = rng.standard_normal((s, h)).astype(np.float32)
    router = rng.standard_normal((h, e)).astype(np.float32)
    logits = np.asarray(jnp.dot(x, router,
                                precision=jax.lax.Precision.HIGHEST))
    prob = np.exp(logits - logits.max(1, keepdims=True))
    prob /= prob.sum(1, keepdims=True)
    eid, wts = moe.route(jnp.asarray(x), jnp.asarray(router),
                         jnp.zeros((e,), jnp.float32), 4, 1.0,
                         score_func="softmax")
    want = np.argsort(-logits, axis=1, kind="stable")[:, :4]
    assert np.array_equal(np.sort(np.asarray(eid), 1), np.sort(want, 1))
    chosen = np.take_along_axis(prob, np.asarray(eid), 1)
    close(wts, chosen / chosen.sum(1, keepdims=True), 1e-6)
    assert float(jnp.abs(jnp.sum(wts, axis=1) - 1.0).max()) < 1e-6
    sig_eid, sig_wts = moe.route(jnp.asarray(x), jnp.asarray(router),
                                 jnp.zeros((e,), jnp.float32), 4, 1.0)
    assert np.array_equal(np.sort(np.asarray(sig_eid), 1), np.sort(want, 1))
    assert float(jnp.abs(sig_wts - wts).max()) > 1e-3
    # and the reference's router
    c = ref.config(dict(TOY))
    theirs = ref.route({"ffn_experts_router_weight": jnp.asarray(router)},
                       "", jnp.asarray(x), c)
    assert np.array_equal(np.sort(np.asarray(theirs["eid"]), 1),
                          np.sort(want, 1))


def expert_inputs(seed, rows=64, h=12, e=16, held=4, f=10):
    inputs = rng_inputs(seed, data=(rows, h), router_weight=(h, e),
                        gate_weight=(held, h, f), up_weight=(held, h, f),
                        down_weight=(held, f, h))
    inputs["router_weight"] = 2.0 * inputs["router_weight"]
    return inputs


def experts_net(inputs, **op):
    e, held = inputs["router_weight"].shape[1], inputs["up_weight"].shape[0]
    return sym.RoutedExperts(num_experts=e, num_held=held, first_held=2,
                             top_k=3, num_hidden=inputs["up_weight"].shape[2],
                             gated=True, score_func="softmax", name="x",
                             **op, **{k: sym.Variable(k) for k in inputs})


def plain_softmax_experts(data, router_weight, gate_weight, up_weight,
                          down_weight, first=2, top_k=3):
    """Dense softmax routing: every held expert computes every row, the
    chosen probabilities over their sum weigh them."""
    prob = jax.nn.softmax(jnp.dot(data, router_weight,
                                  precision=jax.lax.Precision.HIGHEST), -1)
    _, eid = jax.lax.top_k(prob, top_k)
    chosen = jnp.take_along_axis(prob, eid, axis=1)
    wts = chosen / chosen.sum(axis=1, keepdims=True)
    out = jnp.zeros_like(data)
    for j in range(up_weight.shape[0]):
        w = jnp.sum(jnp.where(eid == first + j, wts, 0.0), axis=1)
        a = jax.nn.silu(data @ gate_weight[j]) * (data @ up_weight[j])
        out = out + (a @ down_weight[j]) * w[:, None]
    return out


def test_softmax_routed_experts_against_a_dense_loop():
    """The op with ``score_func="softmax"`` and no auxiliary loss: output
    and every gradient, the router's through the softmax, against the dense
    loop's autodiff; counted once."""
    inputs = expert_inputs(7)
    net = experts_net(inputs)
    telemetry.reset()
    telemetry.enable()
    try:
        against(plain_softmax_experts, net, inputs, tol=5e-5)
        assert telemetry.peek("lower.experts_score.softmax") >= 1
        assert not telemetry.peek("lower.experts_score.sigmoid")
    finally:
        telemetry.disable()


def test_the_auxiliary_loss_reaches_the_router_as_autodiff_gives_it():
    """``aux_loss_coef`` c: the op's backward pass gives every input the
    gradient of ``sum(y * head) + c L_aux``, ``L_aux = E sum_e f_e P_e``
    (``f`` the shares of rows by expert, no gradient; ``P`` the mean
    probability; ALL experts, held or not): the output is untouched, the
    router's and the data's gradients move by exactly c times autodiff's of
    ``L_aux``, the experts' weights' not at all."""
    c = 0.3
    inputs = expert_inputs(8)
    # a small head, so that the loss's share of the gradients is not lost
    # in the rounding of the rest
    head = 1e-3 * rng_inputs(9, y=inputs["data"].shape)["y"]

    def run(coef):
        return run_op(experts_net(inputs, aux_loss_coef=coef), inputs, head)

    (y0, g0), (y1, g1) = run(0.0), run(c)
    assert np.array_equal(np.asarray(y0), np.asarray(y1))

    def l_aux(data, router):
        prob = jax.nn.softmax(jnp.dot(data, router,
                                      precision=jax.lax.Precision.HIGHEST),
                              -1)
        _, eid = jax.lax.top_k(prob, 3)
        f = jax.lax.stop_gradient(ref.loads(eid, prob.shape[1])) \
            / prob.shape[0]
        return prob.shape[1] * jnp.sum(f * jnp.mean(prob, axis=0))

    value, (d_data, d_router) = jax.value_and_grad(l_aux, argnums=(0, 1))(
        jnp.asarray(inputs["data"]), jnp.asarray(inputs["router_weight"]))
    # the statement of the loss the op's module makes, and the reference's
    prob = jax.nn.softmax(jnp.asarray(inputs["data"])
                          @ jnp.asarray(inputs["router_weight"]), -1)
    load = ref.loads(jax.lax.top_k(prob, 3)[1], prob.shape[1])
    assert float(moe.aux_loss(prob, load)) == pytest.approx(float(value),
                                                            rel=1e-6)
    assert float(ref.aux_loss(prob, load)) == pytest.approx(float(value),
                                                            rel=1e-6)
    assert float(jnp.abs(d_router).max()) > 1e-4
    assert float(jnp.abs(c * d_router).max()) \
        > 1e-2 * float(np.abs(g0["router_weight"]).max())
    close(g1["router_weight"], np.asarray(g0["router_weight"]) + c * d_router,
          2e-5)
    close(g1["data"], np.asarray(g0["data"]) + c * d_data, 2e-5)
    for name in ("gate_weight", "up_weight", "down_weight"):
        assert np.array_equal(np.asarray(g1[name]), np.asarray(g0[name]))


def test_bad_scores_are_refused():
    op = dict(num_experts=32, num_held=8, top_k=4, num_hidden=8, gated=True)
    with pytest.raises(mx.base.MXNetError, match="score_func"):
        moe.RoutedExperts(score_func="tanh", **op).infer_shape(
            [(16, 8)] + [None] * 4)
    with pytest.raises(mx.base.MXNetError, match="below 0"):
        moe.RoutedExperts(aux_loss_coef=-0.1, **op).infer_shape(
            [(16, 8)] + [None] * 4)
    moe.RoutedExperts(score_func="softmax", aux_loss_coef=0.001,
                      **op).infer_shape([(16, 8)] + [None] * 4)


def test_expert_shares_add_up_to_the_uncut_layer():
    """``model-configs`` section 4: at 32 experts, 2 held a share, the
    SIXTEEN shares' routed parts as the program computes them plus the
    gated shared expert counted once equal the uncut reference's expert
    layer; each share's part is what the reference given that share
    computes."""
    args = dict(TOY, layer_types=["linear_attention"], experts_held=32,
                first_expert=0)
    params = ref.init_params(args, jax.random.PRNGKey(9))
    x = jnp.asarray(rng_inputs(9, x=(48, TOY["hidden"]))["x"])
    pre = "layer0_"
    whole, routing = ref.experts(params, pre, x, args)
    assert float(routing["load"].sum()) == 48 * TOY["top_k"]
    st, mm = ref._ROUND[None]
    c = ref.config(args)
    total = np.asarray(ref.shared_part(params, pre, x, st, mm))
    inputs = {n: np.asarray(params[pre + "ffn_experts_%s_weight" % n])
              for n in ("router", "gate", "up", "down")}
    names = ["data"] + [n + "_weight" for n in inputs]
    v = {k: sym.Variable(k) for k in names}
    for first in range(0, 32, 2):
        net = sym.RoutedExperts(
            num_experts=32, num_held=2, first_held=first, top_k=TOY["top_k"],
            gated=True, score_func="softmax", aux_loss_coef=0.01,
            num_hidden=TOY["expert_hidden"], **v)
        mine = {"data": np.asarray(x), "router_weight": inputs["router"]}
        mine.update({n + "_weight": inputs[n][first:first + 2]
                     for n in ("gate", "up", "down")})
        ex = net.bind(mx.cpu(), {k: mx.nd.array(a) for k, a in mine.items()},
                      aux_states=aux_states(
                          net, {k: a.shape for k, a in mine.items()}))
        part = ex.forward(is_train=False)[0].asnumpy()
        theirs = ref.routed_part(
            x, ref.route(params, pre, x, c),
            tuple(jnp.asarray(mine[n + "_weight"])
                  for n in ("gate", "up", "down")), first, st, mm)
        close(part, np.asarray(theirs), 5e-5)
        total = total + part
    close(total, np.asarray(whole), 5e-5)


def test_balanced_start_moves_the_routers_alone_and_evens_the_loads():
    """``init.balance``: a descent on the auxiliary loss from skewed
    routers (a few columns drawn larger) on one drawn sequence; only the
    routers differ from the plain draw, and each layer's largest load falls
    towards the mean."""
    key = jax.random.PRNGKey(3)
    plain = ref.init_params(TOY, key)
    skew = {k: np.asarray(v) * np.where(np.arange(v.shape[1]) < 4, 4.0, 1.0)
            for k, v in plain.items() if k.endswith("router_weight")}
    params = dict(plain, **{k: jnp.asarray(v) for k, v in skew.items()})
    ids = ref.zipf_ids(jax.random.fold_in(key, 999), TOY["vocab"],
                       TOY["seq_len"], 1.0)
    rates = ref.balance_rates({"from": 0.3, "to": 0.01, "steps": 80,
                               "hold": 20})
    routers, before, after = ref.balanced_start(TOY, params, ids, rates)
    assert set(routers) == set(skew)
    mean = TOY["seq_len"] * TOY["top_k"] / TOY["experts_total"]
    for name in routers:
        assert before[name].sum() == after[name].sum() \
            == TOY["seq_len"] * TOY["top_k"]
        assert after[name].max() < before[name].max()
        assert after[name].max() <= 2.5 * mean < before[name].max()
    # through init_params: everything but the routers is the plain draw
    balanced = ref.init_params(TOY, key, {"balance": {
        "from": 0.3, "to": 0.01, "steps": 20, "hold": 5}})
    for k, v in plain.items():
        same = np.array_equal(np.asarray(v), np.asarray(balanced[k]))
        assert same != k.endswith("router_weight"), k


# ---------------------------------------------------------------------------
# the model through Module.fit
# ---------------------------------------------------------------------------
COUNTERS = ("step.dispatches", "step.fused_steps", "step.fused_fallback",
            "lower.delta_rule_heads.grouped", "lower.delta_rule_heads.equal",
            "lower.delta_rule_gate.head",
            "lower.delta_rule_kernel.xla_chunked",
            "lower.delta_rule_kernel.pallas_chunked",
            "lower.attention_kernel.xla_blockwise",
            "lower.attention_kernel.pallas_splash",
            "lower.experts_score.softmax", "lower.experts_score.sigmoid",
            "lower.experts_body.swiglu", "lower.experts_kernel.xla_loop",
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
    net = get_qwen3_next(**toy)
    assert set(params0) == set(net.list_arguments()) - {"data",
                                                        "softmax_label"}
    mod = mx.mod.Module(net, context=mx.cpu(0))
    telemetry.reset()
    telemetry.enable()
    try:
        mod.fit(Ring(batches), eval_metric="ce", optimizer="adam",
                optimizer_params=dict(RECIPE), initializer=None,
                arg_params={k: mx.nd.array(v) for k, v in params0.items()},
                num_epoch=1)
        counters = {k: telemetry.peek(k) for k in COUNTERS}
        counters["jit_entries"] = telemetry.peek("step.fused_jit_entries",
                                                 "gauge")
    finally:
        telemetry.disable()
    return mod, params0, counters


def follow_toy(batches, params0, toy=TOY):
    return ref.follow(toy, RECIPE, params0,
                      [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
                      rows=np.arange(16).reshape(2, 8))


def leaf_gaps(got, want):
    return {k: abs(float(got[k]) - want[k]) / max(want[k], 1e-3)
            for k in want}


def test_model_fits_on_the_fused_step_like_the_reference(monkeypatch):
    """Three Adam steps of two sequences through ``Module.fit`` under
    recomputation against the benchmark's reference, the auxiliary loss at
    ten times the published weight so that it shows: the first gradient
    (Adam's first moment) and the three-step change by leaf; one dispatch a
    step, one program; the lowerings and the experts' rows as telemetry
    reads them. Tolerances as the siblings': float32 on both sides, the
    chunked delta rule against the recurrence and grouped experts against a
    masked loop: the median leaf to 2e-4, the worst to 1e-2."""
    batches = toy_batches(3, toy=TOY)
    mod, params0, counters = fit_toy(monkeypatch, batches)
    assert mod._fused_step_active
    assert counters["step.dispatches"] == 3
    assert counters["step.fused_steps"] == 3
    assert not counters["step.fused_fallback"]
    assert counters["jit_entries"] == 1
    assert counters["lower.delta_rule_heads.grouped"] == 3
    assert not counters["lower.delta_rule_heads.equal"]
    assert counters["lower.delta_rule_gate.head"] == 3
    # 8 keys and 8 values a head are whole sublanes: PR 31's chunk kernels,
    # interpreted here, under the repeated key heads
    assert counters["lower.delta_rule_kernel.pallas_chunked"] == 3
    assert not counters["lower.delta_rule_kernel.xla_chunked"]
    assert counters["lower.attention_kernel.xla_blockwise"] == 1
    assert counters["lower.experts_score.softmax"] == 4
    assert not counters["lower.experts_score.sigmoid"]
    assert counters["lower.experts_body.swiglu"] == 4
    assert counters["lower.experts_kernel.xla_loop"] == 4
    # (row, expert) pairs: 4 expert layers x 3 steps x 128 rows x top-4
    assert counters["moe.rows_total"] == 4 * 3 * 128 * 4
    assert 0 < counters["moe.rows_here"] < counters["moe.rows_total"]
    assert counters["moe.dropped_rows"] == 0
    assert counters["remat.segments_recomputed"] \
        == counters["remat.segments"] - 1 > 0
    args, aux = mod.get_params()
    assert set(args) == set(params0)
    # the selection biases are states of the op that this family never moves
    assert all(not v.asnumpy().any() for k, v in aux.items()
               if k.endswith("select_bias"))
    want = follow_toy(batches, params0)
    delta = ref.leaf_norms({k: jnp.asarray(args[k].asnumpy() - params0[k])
                            for k in params0})
    assert set(delta) == set(want["delta_norms"])
    assert sum("experts_up_weight[" in k for k in delta) \
        == 4 * TOY["experts_held"]
    gaps = sorted(leaf_gaps(delta, want["delta_norms"]).values())
    assert gaps[len(gaps) // 2] < 2e-4 and gaps[-1] < 1e-2, gaps[-3:]
    assert all(n > 0 for n in want["delta_norms"].values())
    # the first gradient, from Adam's first moment after ONE step from a
    # zero state: m1 = (1 - b1) g
    mod, _, _ = fit_toy(monkeypatch, batches[:1])
    grads = {name: jnp.asarray(mod._updater.states[i][0].asnumpy()
                               / (1.0 - RECIPE["beta1"]))
             for i, name in enumerate(mod._param_names)}
    norms = ref.leaf_norms(grads)
    assert set(norms) == set(want["grad_norms"])
    for name, norm in norms.items():
        assert abs(float(norm) - want["grad_norms"][name]) \
            <= 2e-3 * max(want["grad_norms"][name], 1e-3), name
    # and the auxiliary loss is IN those gradients: without it the
    # reference's routers' first gradients differ by more than that
    without = follow_toy(batches[:1], params0,
                         dict(TOY, aux_loss_coef=0.0))["grad_norms"]
    routers = [k for k in norms if k.endswith("router_weight")]
    assert len(routers) == 4
    assert max(abs(without[k] - want["grad_norms"][k])
               / want["grad_norms"][k] for k in routers) > 2e-2
    # what lies behind every router reads no auxiliary loss
    rest = [k for k in norms if k in ("lm_head_weight", "final_norm_gamma")
            or k.startswith("layer3_ffn_experts_down")]
    assert len(rest) == 2 + TOY["experts_held"]
    assert max(abs(without[k] - want["grad_norms"][k])
               / max(want["grad_norms"][k], 1e-3) for k in rest) < 2e-3


def test_model_loss_and_logprob_follow_the_reference(monkeypatch):
    """The forward pass alone: the program's loss of the first batch (the
    metric's cross-entropy, no auxiliary term in it) against the
    reference's, float32."""
    batches = toy_batches(1, toy=TOY)
    _, params0, _ = fit_toy(monkeypatch, batches)
    ids, labels = (jnp.asarray(x) for x in batches[0])
    with jax.default_matmul_precision("highest"):
        want, _ = ref.loss_and_logprob(
            {k: jnp.asarray(v) for k, v in params0.items()}, ids, labels,
            TOY, jnp.arange(4))
    it = Ring(batches)
    metric = mx.metric.create("ce")
    fresh = mx.mod.Module(get_qwen3_next(**TOY), context=mx.cpu(0))
    fresh.bind(it.provide_data, it.provide_label, for_training=False)
    fresh.set_params({k: mx.nd.array(v) for k, v in params0.items()}, {},
                     allow_missing=True)
    fresh.forward(it.next(), is_train=False)
    fresh.update_metric(metric, [mx.nd.array(batches[0][1])])
    assert metric.get()[1] == pytest.approx(float(want), rel=2e-5)


def test_bad_layers_are_refused():
    with pytest.raises(ValueError, match="not 'linear_attention'"):
        get_qwen3_next(**dict(TOY, layer_types=["linear_attention", "kda"]))
    with pytest.raises(ValueError, match="unknown arguments"):
        ref.config(dict(TOY, n_group=4))


def test_published_defaults_are_the_catalogs():
    """The factory's and the reference's defaults are the published sizes:
    48 layers, gated attention at published 3, 7, ..., 47; the parameter
    count of the cut the configuration states, and no width of the
    configuration differs from the catalog's row."""
    from mxnet_tpu.models import qwen3_next as model

    assert ref.LAYER_TYPES.count("full_attention") == 12
    assert [i for i, k in enumerate(ref.LAYER_TYPES)
            if k == "full_attention"] == list(range(3, 48, 4))
    assert model.LAYER_TYPES == ref.LAYER_TYPES
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs", CONFIG)) as f:
        config = json.load(f)
    assert config["model"]["args"] == config["reference"]["args"]
    args = config["reference"]["args"]
    assert args["layer_types"] == list(ref.LAYER_TYPES[:4])
    published = dict(ref.DEFAULTS, layer_types=args["layer_types"],
                     vocab=args["vocab"], experts_held=args["experts_held"])
    assert ref.config(args) == ref.config(published)
    assert (config["hidden_size"], config["head_dim"],
            config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["partial_rotary_factor"] * config["head_dim"]) \
        == (args["hidden"], args["head_dim"], args["linear_key_heads"],
            args["linear_value_heads"], args["expert_hidden"], args["top_k"],
            args["rotary_dim"])
    assert set(config["reduced"]) == set(config["published"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    n = sum(int(np.prod(s)) for s in ref.param_shapes(args).values())
    assert n == 625667136 and "625.67 M" in config["deployment"]
    cost = ref.step_cost(args, config["tokens"]["batch"])
    assert config["tokens"] == {"batch": 1, "seq_len": 8192}
    assert cost["params"] == n
    assert set(cost["parts"]) == {
        "linattn_proj_conv", "linattn_scan", "attention_proj",
        "attention_kernel", "moe_grouped_matmul", "moe_rest", "lm_head_loss",
        "embed"}
    # the recurrence's useful work: 7 K V a position and VALUE head
    assert cost["parts"]["linattn_scan"][0] \
        == 3 * 3 * 7 * 8192 * 32 * 128 * 128
    # attention at 256 key and 256 value columns, the causal half, 16 query
    # heads
    assert cost["parts"]["attention_kernel"][0] \
        == 3 * 8192 * 8192 * 16 * (256 + 256)
    # the held experts by the even share: 8,192 x 10 x 32 / 512 rows
    assert cost["parts"]["moe_grouped_matmul"][0] \
        == 3 * 4 * 3 * 2 * 5120 * 2048 * 512


def test_reference_imports_nothing_of_the_program():
    import inspect

    src = inspect.getsource(ref)
    assert "mxnet_tpu" not in src.replace("mxnet_tpu/optimizer.py", "")


# ---------------------------------------------------------------------------
# the toy preset's fused step, from its lowering (tests/test_hlo_gates.py)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def toy_step():
    return lower_language_toy(CONFIG, get_qwen3_next(**TOY),
                              *toy_batches(1, toy=TOY)[0])


def test_the_toy_step_donates_every_master_moment_and_state(toy_step):
    check_state_is_donated(*toy_step)


def test_the_toy_step_takes_bfloat16_products_but_where_named(toy_step):
    check_products_are_bfloat16(*toy_step[:2], {
        # ``GatedDeltaRule`` computes in float32 whatever the compute
        # dtype (a bfloat16 operand is another result: ``ops/seq.py``)
        "seq": 94,
        # the router's scores, float32 from the normed rows (a choice of
        # experts is discontinuous: ``moe.route``): forward, recomputed,
        # and the two gradients, a layer of experts
        "RoutedExperts": 16,
        # toy widths take ``attend_blockwise``, whose backward pass takes
        # the float32 scores' cotangent against operands widened to it; the
        # cells' heads take the splash kernel (tests/test_cell_lowering.py)
        "attention": 4})
