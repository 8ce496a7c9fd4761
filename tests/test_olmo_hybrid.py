"""``models.get_olmo_hybrid`` (gated delta-rule linear attention, QK-normed
full attention without rotary, a dense SwiGLU feed-forward, norms after
each) through ``Module.fit`` on the fused step against the benchmark's
float32 reference, the operators' new forms against plain ``jax.numpy``,
and the share by heads of ``model-configs`` section 4. Toy widths, seeded."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu import telemetry
from mxnet_tpu.models import get_olmo_hybrid
from mxnet_tpu.models import olmo_hybrid as model

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import olmo_hybrid as ref  # noqa: E402
from test_nemotron_h import Ring, against, close, rng_inputs  # noqa: E402
from test_hlo_gates import (check_products_are_bfloat16,  # noqa: E402
                            check_state_is_donated, lower_language_toy)

TOY = dict(layer_types=["linear_attention", "linear_attention",
                        "linear_attention", "full_attention"],
           hidden=32, vocab=96, heads=4, heads_held=2, first_head=0,
           head_dim=8, linear_key_dim=6, linear_value_dim=12, ffn_hidden=48,
           seq_len=24, chunk=16)
# the delta rule at widths of whole sublanes: the Pallas chunk kernels (the
# interpreter here); TOY's widths keep the XLA body
SUBLANE = dict(TOY, linear_key_dim=8, linear_value_dim=16)
BODIES = pytest.mark.parametrize(
    "toy,body", [(TOY, "xla_chunked"), (SUBLANE, "pallas_chunked")],
    ids=["xla", "pallas"])
RECIPE = {"learning_rate": 0.001, "wd": 0.01, "beta1": 0.9, "beta2": 0.95,
          "epsilon": 1e-8, "rescale_grad": 1.0}


# ---------------------------------------------------------------------------
# the operators' new forms
# ---------------------------------------------------------------------------
def test_rmsnorm_gates_after_under_one_gamma_a_head():
    """A head's norm, ONE gamma of a head's width, the gate after."""
    rows, heads, width = 10, 3, 8
    inputs = rng_inputs(0, data=(rows, heads * width), gamma=(width,),
                        gate=(rows, heads * width))

    def plain(data, gamma, gate):
        x = data.reshape(rows, heads, width)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return (x * gamma).reshape(rows, -1) * jax.nn.silu(gate)

    net = sym.RMSNorm(data=sym.Variable("data"), gamma=sym.Variable("gamma"),
                      gate=sym.Variable("gate"), gated=True, gate_after=True,
                      num_groups=heads, shared_gamma=True, eps=1e-6,
                      name="n")
    against(plain, net, inputs)
    # the two switches are apart: Mamba-2's order under a shared gamma
    before = sym.RMSNorm(data=sym.Variable("data"),
                         gamma=sym.Variable("gamma"),
                         gate=sym.Variable("gate"), gated=True,
                         num_groups=heads, shared_gamma=True, eps=1e-6)

    def plain_before(data, gamma, gate):
        x = (data * jax.nn.silu(gate)).reshape(rows, heads, width)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return (x * gamma).reshape(rows, -1)

    against(plain_before, before, inputs)
    with pytest.raises(mx.MXNetError, match="gate_after"):
        sym.RMSNorm(data=sym.Variable("data"), gate_after=True).infer_shape(
            data=(4, 8))


def test_attention_of_one_head_groups_without_rotary_whole_lanes():
    """As many key/value heads as query heads (one-head groups through the
    splash kernel's multi-query form, interpreted here), no rotary, queries
    and keys through RMSNorm first: against the reference's mixer."""
    toy = dict(TOY, layer_types=["full_attention"], heads=2, heads_held=2,
               head_dim=128, seq_len=128, hidden=16)
    params = {k: np.asarray(v) for k, v in ref.init_params(
        toy, jax.random.PRNGKey(2)).items() if k.startswith("layer0_")}
    params["layer0_qnorm_gamma"] = 1.0 + 0.1 * rng_inputs(
        1, g=(256,))["g"]
    x = rng_inputs(4, x=(128, 16))["x"]
    net = model._full_attention(sym.Variable("x"), "layer0", 128, 2, 128, 16,
                                1e-6)
    names = [n for n in net.list_arguments() if n != "x"]

    def plain(x, **p):
        return ref.mixer(p, "layer0_", "full_attention", x, toy)

    telemetry.reset()
    telemetry.enable()
    try:
        against(plain, net, {"x": x, **{n: params[n] for n in names}},
                tol=2e-4)
        assert telemetry.peek("lower.attention_kernel.pallas_splash") >= 1
    finally:
        telemetry.disable()


# ---------------------------------------------------------------------------
# the model through Module.fit
# ---------------------------------------------------------------------------
def toy_batches(n, batch=2, seed=11, toy=TOY):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        ids = rng.integers(0, toy["vocab"], (batch, toy["seq_len"] + 1))
        out.append((ids[:, :-1].astype(np.int32),
                    ids[:, 1:].astype(np.int32)))
    return out


COUNTERS = ("step.dispatches", "step.fused_steps", "step.fused_fallback",
            "lower.delta_rule_kernel.xla_chunked",
            "lower.delta_rule_kernel.pallas_chunked",
            "lower.attention_kernel.xla_blockwise", "remat.segments",
            "remat.segments_recomputed", "remat.kept_results")


def fit_toy(monkeypatch, batches, compute_dtype=None, toy=TOY, seed=5,
            metric="ce"):
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    if compute_dtype:
        monkeypatch.setenv("MXNET_COMPUTE_DTYPE", compute_dtype)
    params0 = {k: np.asarray(v) for k, v in ref.init_params(
        toy, jax.random.PRNGKey(seed)).items()}
    mod = mx.mod.Module(get_olmo_hybrid(**toy), context=mx.cpu(0))
    telemetry.reset()
    telemetry.enable()
    try:
        mod.fit(Ring(batches), eval_metric=metric, optimizer="adam",
                optimizer_params=dict(RECIPE), initializer=None,
                arg_params={k: mx.nd.array(v) for k, v in params0.items()},
                num_epoch=1)
        counters = {k: telemetry.peek(k) for k in COUNTERS}
        counters["jit_entries"] = telemetry.peek("step.fused_jit_entries",
                                                 "gauge")
    finally:
        telemetry.disable()
    return mod, params0, counters


@BODIES
def test_model_fits_on_the_fused_step_like_the_reference(monkeypatch, toy,
                                                         body):
    """Three Adam steps through ``Module.fit`` under recomputation against
    the benchmark's reference: the first gradient (Adam's first moment)
    and the three-step change by leaf, one dispatch a step, one program;
    with the delta rule's XLA body and with its chunk kernels."""
    batches = toy_batches(3)
    mod, params0, counters = fit_toy(monkeypatch, batches, toy=toy)
    assert mod._fused_step_active
    assert counters["step.dispatches"] == 3
    assert counters["step.fused_steps"] == 3
    assert not counters["step.fused_fallback"]
    assert counters["jit_entries"] == 1
    other = {"xla_chunked", "pallas_chunked"} - {body}
    assert counters["lower.delta_rule_kernel." + body] >= 3
    assert not counters["lower.delta_rule_kernel." + other.pop()]
    assert counters["lower.attention_kernel.xla_blockwise"] >= 1
    # under recomputation the delta rule's result and attention's are kept
    assert counters["remat.segments_recomputed"] \
        == counters["remat.segments"] - 1 > 0
    assert counters["remat.kept_results"] >= 3
    assert set(mod.get_params()[0]) == set(params0)
    assert not mod.get_params()[1]
    want = ref.follow(toy, RECIPE, params0,
                      [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
                      rows=np.arange(16).reshape(2, 8))
    got = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    delta = ref.leaf_norms({k: jnp.asarray(got[k] - params0[k])
                            for k in params0})
    # by leaf; Adam's step is m / sqrt(v), so a leaf of two numbers (a
    # decay's) moves by a sign's worth on the fifth digit of a gradient
    gaps = sorted(abs(float(delta[k]) - want["delta_norms"][k])
                  / max(want["delta_norms"][k], 1e-3) for k in delta)
    assert gaps[len(gaps) // 2] < 2e-4 and gaps[-1] < 1e-2, gaps[-3:]
    assert all(n > 0 for n in want["delta_norms"].values())
    # the first gradient, from Adam's first moment after ONE step from a
    # zero state: m1 = (1 - b1) (g + wd w0)
    mod, _, _ = fit_toy(monkeypatch, batches[:1], toy=toy)
    for i, name in enumerate(mod._param_names):
        m1 = mod._updater.states[i][0].asnumpy()
        g = m1 / (1.0 - RECIPE["beta1"]) - RECIPE["wd"] * params0[name]
        norm = float(np.sqrt((g * g).sum()))
        assert abs(norm - want["grad_norms"][name]) \
            <= 2e-3 * max(want["grad_norms"][name], 1e-3), name


@BODIES
def test_model_loss_follows_the_reference(monkeypatch, toy, body):
    """Step by step: the metric's mean cross-entropy after each step."""
    batches = toy_batches(3, seed=12)
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
        toy, jax.random.PRNGKey(6)).items()}
    mod = mx.mod.Module(get_olmo_hybrid(**toy), context=mx.cpu(0))
    mod.fit(it, eval_metric=it.metric, optimizer="adam",
            optimizer_params=dict(RECIPE), initializer=None,
            arg_params={k: mx.nd.array(v) for k, v in params0.items()},
            num_epoch=1)
    per_step = np.diff([0.0] + losses + [it.metric.get()[1] * 3])
    want = ref.follow(toy, RECIPE, params0,
                      [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
                      rows=np.arange(8).reshape(2, 4))
    np.testing.assert_allclose(per_step, want["losses"], rtol=2e-4)


@BODIES
def test_model_trains_in_bfloat16_with_float32_decays(monkeypatch, toy, body):
    batches = toy_batches(6, seed=13)
    mod, params0, counters = fit_toy(monkeypatch, batches,
                                     compute_dtype="bfloat16", toy=toy)
    # the op computes in float32 whatever the compute dtype: the same body
    assert counters["lower.delta_rule_kernel." + body] >= 3
    assert counters["step.dispatches"] == 6
    assert not counters["step.fused_fallback"]
    assert counters["jit_entries"] == 1
    got, _ = mod.get_params()
    assert all(np.isfinite(v.asnumpy()).all() for v in got.values())
    # the decay's parameters trained (they reach the op in float32:
    # ``GatedDeltaRule.full_precision_args``)
    assert np.abs(got["layer0_delta_A_log"].asnumpy()
                  - params0["layer0_delta_A_log"]).max() > 0
    from mxnet_tpu.ops.seq import GatedDeltaRule
    assert GatedDeltaRule.full_precision_args == ("A_log", "dt_bias")


def test_reference_imports_nothing_of_the_program():
    src = open(ref.__file__).read()
    assert "import mxnet_tpu" not in src and "from mxnet_tpu" not in src
    assert "solve_triangular" not in src and "cumsum" not in src


def test_bad_shares_and_layer_types_are_refused():
    with pytest.raises(ValueError, match="heads 3..5 of 4"):
        get_olmo_hybrid(**dict(TOY, first_head=3))
    with pytest.raises(ValueError, match="layer 0"):
        get_olmo_hybrid(**dict(TOY, layer_types=["sliding_attention"]))


# ---------------------------------------------------------------------------
# the share by heads (model-configs section 4)
# ---------------------------------------------------------------------------
def _share(params, kind, first, held, toy):
    """The weights of heads ``first .. first + held`` of one uncut mixer:
    rows of the projections, columns of the output projection; the one
    gamma of the gated norm is every share's."""
    key, value, head = (toy[k] for k in ("linear_key_dim",
                                         "linear_value_dim", "head_dim"))
    widths = {"q": key, "k": key, "v": value, "g": value, "a": 1, "b": 1,
              "delta": 1, "o": value} if kind == "linear_attention" \
        else dict.fromkeys(("q", "k", "v", "o", "qnorm", "knorm"), head)
    out = {}
    for name, array in params.items():
        part = name[len("layer0_"):].split("_")[0].replace("conv", "")
        if part == "gnorm":
            out[name] = array
            continue
        lo, hi = first * widths[part], (first + held) * widths[part]
        out[name] = array[:, lo:hi] if part == "o" else array[lo:hi]
    return out


@pytest.mark.parametrize("kind,toy", [
    ("linear_attention", TOY), ("linear_attention", SUBLANE),
    ("full_attention", TOY)],
    ids=["linear_attention", "linear_attention-pallas", "full_attention"])
def test_head_shares_add_up_to_the_uncut_mixer(kind, toy):
    """Two chips share a layer by heads: what each computes of ``Mixer(x)``
    from its half of the heads adds up to the uncut reference's. For the
    gated delta rule exactly, program and reference alike (a head sees only
    itself). For full attention when each half is given the mean square of
    ALL the columns, which the exchange would bring (the reference's
    ``qk_ms``); with the mean square of the columns held, as the benchmark
    runs it without the exchange, the gap is printed. The feed-forward, the
    norms and the head are whole on every chip: nothing of them adds up."""
    uncut = dict(toy, layer_types=[kind], heads_held=4)
    half = dict(uncut, heads_held=2)
    mix = {k: jnp.asarray(v) for k, v in ref.init_params(
        uncut, jax.random.PRNGKey(9)).items()
        if k.startswith("layer0_") and "ffn" not in k and "mixer" not in k}
    if kind == "full_attention":
        for n in ("layer0_qnorm_gamma", "layer0_knorm_gamma"):
            mix[n] = 1.0 + 0.2 * jnp.asarray(rng_inputs(
                3, g=mix[n].shape)["g"])
    x = jnp.asarray(rng_inputs(7, x=(2 * toy["seq_len"], toy["hidden"]))["x"])
    whole = ref.mixer(mix, "layer0_", kind, x, uncut)
    shares = [_share(mix, kind, first, 2, toy) for first in (0, 2)]
    qk_ms = ref.qk_mean_squares(mix, "layer0_", x) \
        if kind == "full_attention" else None
    parts = [ref.mixer(s, "layer0_", kind, x, dict(half, first_head=f),
                       qk_ms=qk_ms) for s, f in zip(shares, (0, 2))]
    close(parts[0] + parts[1], whole, tol=2e-5)
    assert float(jnp.abs(parts[0]).max()) > 1e-3
    # the program, each share built at the held heads' width
    build = model._linear_attention if kind == "linear_attention" \
        else model._full_attention
    args = (toy["seq_len"], 2, toy["linear_key_dim"],
            toy["linear_value_dim"], 4, toy["chunk"], True, toy["hidden"],
            1e-6) if kind == "linear_attention" \
        else (toy["seq_len"], 2, toy["head_dim"], toy["hidden"], 1e-6)
    net = build(sym.Variable("x"), "layer0", *args)
    got = []
    for s in shares:
        ex = net.bind(mx.cpu(), {"x": mx.nd.array(np.asarray(x)), **{
            k: mx.nd.array(np.asarray(v)) for k, v in s.items()}},
            grad_req="null")
        got.append(ex.forward(is_train=False)[0].asnumpy())
    if kind == "linear_attention":
        close(got[0] + got[1], whole, tol=2e-5)
    else:
        held = [ref.mixer(s, "layer0_", kind, x, dict(half, first_head=f))
                for s, f in zip(shares, (0, 2))]
        for g, h in zip(got, held):
            close(g, h, tol=2e-5)
        gap = float(jnp.abs(held[0] + held[1] - whole).max()
                    / jnp.abs(whole).max())
        print("full attention, shares normalised by the held columns' mean "
              "square: largest gap to the uncut mixer %.4f of its largest "
              "entry" % gap)
        assert gap > 1e-4


# ---------------------------------------------------------------------------
# the reference's parts and its count of operations and bytes
# ---------------------------------------------------------------------------
FULL = dict(layer_types=ref.LAYER_TYPES, heads_held=15, vocab=12544)


def test_part_of_names_every_node_by_its_block():
    part = ref.part_of(FULL)
    assert part("fwd", "FullyConnected", "layer0_q") == "linattn_proj_conv"
    assert part("bwd", "CausalConv1D", "layer2_vconv") == "linattn_proj_conv"
    assert part("bwd", "GatedDeltaRule", "layer1_delta") == "linattn_scan"
    assert part("fwd", "RMSNorm", "layer1_mixer_norm") == "linattn_proj_conv"
    assert part("fwd", "_Mul", "layer0_ffn_mul") == "dense_ffn"
    assert part("bwd", "RMSNorm", "layer3_ffn_norm") == "dense_ffn"
    assert part("fwd", "RMSNorm", "layer3_qnorm") == "attention_proj"
    assert part("bwd", "CausalAttention", "layer3_attn") == "attention_kernel"
    assert part("fwd", "SoftmaxOutput", "softmax") == "lm_head_loss"
    assert part("metric", "", "") == "lm_head_loss"
    assert part("update", "", "") == "optimizer"
    assert part("fwd", "Embedding", "embed") == "other:Embedding"


def test_cost_of_the_cut_by_hand():
    """ISSUE 30's own count: 44.35 M in a linear mixer at 15 heads, 29.49 M
    in a full one, 126.81 M in a feed-forward, 766.1 M in all."""
    tokens = 8192
    lin = ref.layer_cost("linear_attention", FULL, tokens)
    full = ref.layer_cost("full_attention", FULL, tokens)
    ffn = 3 * 3840 * 11008
    assert lin["dense_ffn"][0] == full["dense_ffn"][0] == 2 * tokens * ffn
    wide = 15 * (96 + 96 + 192 + 192 + 2)
    assert lin["linattn_proj_conv"][0] == 2 * tokens * 3840 * wide \
        + 2 * tokens * 2880 * 3840 + 2 * tokens * 15 * 384 * 4
    # a chunk of 64 and head: 64^2 (3 x 96 + 2 x 192) + 2 x 64 x 96^2
    # + 6 x 64 x 96 x 192 + 2 x 96^2 x 192
    chunk = 4096 * 672 + 128 * 9216 + 384 * 18432 + 2 * 9216 * 192
    assert chunk == 14548992
    assert lin["linattn_scan"][0] == 128 * 15 * chunk
    assert lin["linattn_scan"][1] == tokens * 15 * 578 * 2 \
        + 2 * 128 * 15 * 96 * 192 * 4
    assert full["attention_proj"][0] == 2 * tokens * 3840 * 1920 * 4
    assert full["attention_kernel"][0] == 2 * 8192 * 8192 * 128 * 15
    cost = ref.step_cost(FULL, 1)
    linear = 2 * 1440 * 3840 + 2 * 2880 * 3840 + 2 * 15 * 3840 \
        + 4 * (1440 + 1440 + 2880) + 30 + 192 + 3840 * 2880 + ffn + 2 * 3840
    full_ = 4 * 1920 * 3840 + 2 * 1920 + ffn + 2 * 3840
    assert (linear, full_) == (171195102, 156314880)
    assert cost["params"] == 2 * 12544 * 3840 + 3 * linear + full_ + 3840 \
        == 766241946
    fwd = sum(f for f, _ in cost["parts"].values()) // 3
    assert cost["flops"] == 3 * fwd and cost["recompute_flops"] == fwd
    assert cost["state_bytes"] == cost["params"] * 30
    assert 33e12 < cost["flops"] < 38e12


# ---------------------------------------------------------------------------
# the toy preset's fused step, from its lowering (tests/test_hlo_gates.py)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def toy_step():
    return lower_language_toy("olmo_hybrid_l4_headshare_bf16.json",
                              get_olmo_hybrid(**TOY),
                              *toy_batches(1, toy=TOY)[0])


def test_the_toy_step_donates_every_master_moment_and_state(toy_step):
    check_state_is_donated(*toy_step)


def test_the_toy_step_takes_bfloat16_products_but_where_named(toy_step):
    check_products_are_bfloat16(*toy_step[:2], {
        # ``GatedDeltaRule`` computes in float32 whatever the compute
        # dtype (a bfloat16 operand is another result: ``ops/seq.py``)
        "seq": 94,
        # toy widths take ``attend_blockwise``, whose backward pass takes
        # the float32 scores' cotangent against operands widened to it; the
        # cells' heads take the splash kernel (tests/test_cell_lowering.py)
        "attention": 4})
