"""``models.get_laguna`` (Laguna-XS.2: sliding-window and full attention mixed
three to one over shared key/value heads, more query heads on the windowed
layers, a sigmoid gate a head, YaRN over half of the full layers' head, a
dense layer 0 and sigmoid-routed experts beside a shared one) through
``Module.fit`` on the fused step against the benchmark's float32 reference:
the loss, every gradient leaf and three Adam steps; the wrong readings that
must fail that comparison; the share by experts of ``model-configs`` section
4; the configuration against the catalog's row. ``CausalAttention``'s band and
scaled frequencies themselves are ``tests/test_attention_window.py``'s. Toy
widths, seeded."""
import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu import telemetry
from mxnet_tpu.models import get_laguna

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import laguna as ref  # noqa: E402
from test_hlo_gates import (check_products_are_bfloat16,  # noqa: E402
                            check_state_is_donated, lower_language_toy)
from test_nemotron_h import (Ring, aux_states, close,  # noqa: E402
                             rng_inputs)
from test_olmo_hybrid import toy_batches  # noqa: E402

CONFIG = "laguna_xs2_l5_e32of256_bf16.json"
YARN = dict(yarn_factor=4.0, yarn_original_positions=16, yarn_beta_fast=4.0,
            yarn_beta_slow=1.0, yarn_attention_factor=0.1 * math.log(4.0) + 1)
TOY = dict(layer_types=["full_attention", "sliding_attention",
                        "sliding_attention", "full_attention"],
           heads_per_layer=[6, 8, 8, 6], hidden=32, vocab=96, kv_heads=2,
           head_dim=16, window=16, full_rotary_dim=8, dense_hidden=48,
           experts_total=32, experts_held=8, first_expert=8, top_k=4,
           expert_hidden=16, shared_hidden=16, seq_len=64,
           bias_update_rate=0.01, **YARN)
RECIPE = {"learning_rate": 0.001, "wd": 0.0, "beta1": 0.9, "beta2": 0.95,
          "epsilon": 1e-8, "rescale_grad": 1.0}


# ---------------------------------------------------------------------------
# the share by experts
# ---------------------------------------------------------------------------
def test_expert_shares_add_up_to_the_uncut_layer():
    """``model-configs`` section 4: at 32 experts, 4 held a share, the
    EIGHT shares' routed parts as the program computes them plus the shared
    expert counted once equal the uncut reference's expert layer; each
    share's part is what the reference given that share computes."""
    args = dict(TOY, layer_types=["sliding_attention"], heads_per_layer=[8],
                mlp_layer_types=["sparse"], experts_held=32, first_expert=0)
    params = ref.init_params(args, jax.random.PRNGKey(9))
    x = jnp.asarray(rng_inputs(9, x=(48, TOY["hidden"]))["x"])
    pre = "layer0_"
    whole, routed = ref.experts(params, pre, x, args)
    assert float(routed["load"].sum()) == 48 * TOY["top_k"]
    st, mm = ref._ROUND[None]
    c = ref.config(args)
    total = np.asarray(ref.shared_part(params, pre, x, st, mm))
    inputs = {n: np.asarray(params[pre + "ffn_experts_%s_weight" % n])
              for n in ("router", "gate", "up", "down")}
    names = ["data"] + [n + "_weight" for n in inputs]
    v = {k: sym.Variable(k) for k in names}
    for first in range(0, 32, 4):
        net = sym.RoutedExperts(
            num_experts=32, num_held=4, first_held=first, top_k=TOY["top_k"],
            gated=True, scale=2.5, num_hidden=TOY["expert_hidden"], **v)
        mine = {"data": np.asarray(x), "router_weight": inputs["router"]}
        mine.update({n + "_weight": inputs[n][first:first + 4]
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


def test_balanced_start_moves_the_biases_alone_and_evens_the_loads():
    """``init.balance``: the balancing rule run on one drawn sequence from
    skewed routers (a few columns drawn larger); only the selection biases
    differ from the plain draw, which holds them at 0, and each layer's
    largest load falls towards the mean. Without a ``bias_update_rate`` the
    model has no such state and ``init.balance`` moves nothing."""
    key = jax.random.PRNGKey(3)
    plain = ref.init_params(TOY, key)
    states = {k for k in plain if k.endswith(ref.STATE)}
    assert states == {"layer%d_ffn_experts_select_bias" % i
                      for i in (1, 2, 3)}
    assert all(not np.asarray(plain[k]).any() for k in states)
    params = {k: v * np.where(np.arange(v.shape[1]) < 4, 4.0, 1.0)
              if k.endswith("router_weight") else v
              for k, v in plain.items()}
    ids = ref.zipf_ids(jax.random.fold_in(key, 999), TOY["vocab"],
                       TOY["seq_len"], 1.0)
    spec = {"from": 0.03, "to": 0.001, "steps": 80, "hold": 20}
    none = ref.balance_rates(dict(spec, steps=0, hold=0))
    _, before = ref.balanced_start(TOY, params, ids, none)
    bias, after = ref.balanced_start(TOY, params, ids,
                                     ref.balance_rates(spec))
    assert set(bias) == set(before) == set(after) == states
    mean = TOY["seq_len"] * TOY["top_k"] / TOY["experts_total"]
    for name in states:
        assert before[name].sum() == after[name].sum() \
            == TOY["seq_len"] * TOY["top_k"]
        assert after[name].max() <= 1.5 * mean < before[name].max()
    balanced = ref.init_params(TOY, key, {"balance": spec})
    for k, v in plain.items():
        same = np.array_equal(np.asarray(v), np.asarray(balanced[k]))
        assert same != (k in states), k
    unbiased = dict(TOY, bias_update_rate=0.0)
    assert set(ref.init_params(unbiased, key, {"balance": spec})) \
        == set(plain) - states


# ---------------------------------------------------------------------------
# the model through Module.fit
# ---------------------------------------------------------------------------
COUNTERS = ("step.dispatches", "step.fused_steps", "step.fused_fallback",
            "lower.attention_mask.causal", "lower.attention_mask.window",
            "lower.attention_kernel.xla_blockwise",
            "lower.attention_kernel.pallas_splash",
            "lower.experts_score.softmax", "lower.experts_score.sigmoid",
            "lower.experts_body.swiglu", "lower.experts_kernel.xla_loop",
            "moe.rows_total", "moe.rows_here", "moe.dropped_rows",
            "remat.segments", "remat.segments_recomputed")


def fit_toy(monkeypatch, batches, toy=TOY, seed=5, params0=None):
    """``fit`` over ``batches`` from the reference's seeded weights; also
    Adam's first moments as they stand after the FIRST step."""
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    if params0 is None:
        params0 = {k: np.asarray(v) for k, v in ref.init_params(
            toy, jax.random.PRNGKey(seed)).items()}
    net = get_laguna(**toy)
    states = {k for k in params0 if k.endswith(ref.STATE)}
    assert set(params0) - states == set(net.list_arguments()) - {
        "data", "softmax_label"}
    assert states <= set(net.list_auxiliary_states())
    mod = mx.mod.Module(net, context=mx.cpu(0))
    first = {}

    def after_a_batch(param):
        if param.nbatch == 0:
            first.update({name: mod._updater.states[i][0].asnumpy()
                          for i, name in enumerate(mod._param_names)})

    telemetry.reset()
    telemetry.enable()
    try:
        mod.fit(Ring(batches), eval_metric="ce", optimizer="adam",
                optimizer_params=dict(RECIPE), initializer=None,
                arg_params={k: mx.nd.array(v) for k, v in params0.items()
                            if k not in states},
                aux_params={k: mx.nd.array(params0[k]) for k in states},
                batch_end_callback=after_a_batch, num_epoch=1)
        counters = {k: telemetry.peek(k) for k in COUNTERS}
        counters["jit_entries"] = telemetry.peek("step.fused_jit_entries",
                                                 "gauge")
    finally:
        telemetry.disable()
    return mod, params0, counters, first


def follow_toy(batches, params0, toy=TOY):
    return ref.follow(toy, RECIPE, params0,
                      [(jnp.asarray(i), jnp.asarray(l)) for i, l in batches],
                      rows=np.arange(16).reshape(2, 8))


@pytest.fixture(scope="module")
def fitted():
    """Three Adam steps of two sequences through ``Module.fit`` under
    recomputation, and the reference's three steps from the same weights."""
    batches = toy_batches(3, toy=TOY)
    with pytest.MonkeyPatch.context() as mp:
        mod, params0, counters, first = fit_toy(mp, batches)
    return batches, mod, params0, counters, first, follow_toy(batches,
                                                              params0)


def test_model_fits_on_the_fused_step(fitted):
    """One dispatch a step, one program; the lowerings and the experts' rows
    as telemetry reads them: two causal and two windowed attentions (toy
    heads take the XLA body), three sigmoid-routed expert layers behind the
    dense one, no row dropped, every expert's selection bias moved by the
    rate a step."""
    _, mod, params0, counters, _, _ = fitted
    assert mod._fused_step_active
    assert counters["step.dispatches"] == 3
    assert counters["step.fused_steps"] == 3
    assert not counters["step.fused_fallback"]
    assert counters["jit_entries"] == 1
    assert counters["lower.attention_mask.causal"] == 2
    assert counters["lower.attention_mask.window"] == 2
    assert counters["lower.attention_kernel.xla_blockwise"] == 4
    assert not counters["lower.attention_kernel.pallas_splash"]
    assert counters["lower.experts_score.sigmoid"] == 3
    assert not counters["lower.experts_score.softmax"]
    assert counters["lower.experts_body.swiglu"] == 3
    # (row, expert) pairs: 3 expert layers x 3 steps x 128 rows x top-4
    assert counters["moe.rows_total"] == 3 * 3 * 128 * 4
    assert 0 < counters["moe.rows_here"] < counters["moe.rows_total"]
    assert counters["moe.dropped_rows"] == 0
    assert counters["remat.segments_recomputed"] \
        == counters["remat.segments"] - 1 > 0
    args, aux = mod.get_params()
    states = {k for k in aux if k.endswith(ref.STATE)}
    assert set(args) | states == set(params0) and len(states) == 3
    # the balancing rule: up or down by the rate after each of three steps
    # (an expert that drew exactly the mean of 16 rows stays that step)
    for k in states:
        moved = np.abs(aux[k].asnumpy() - params0[k]) / TOY["bias_update_rate"]
        assert np.allclose(moved, np.round(moved), atol=1e-3), k
        assert moved.max() <= 3.001 and (moved > 0.5).mean() > 0.5, k


def test_three_adam_steps_follow_the_reference_by_leaf(fitted):
    """The three-step change of every leaf (each held expert's slice of the
    stacked weights its own) against the reference's autodiff and Adam.
    Float32 on both sides, the band of blocks against the written-out mask
    and grouped experts against a masked loop: the median leaf to 2e-4, the
    worst to 1e-2 (a leaf whose change is near nothing)."""
    _, mod, params0, _, _, want = fitted
    args, aux = mod.get_params()
    delta = ref.leaf_norms({k: jnp.asarray(
        (args[k] if k in args else aux[k]).asnumpy() - params0[k])
        for k in params0})
    assert set(delta) == set(want["delta_norms"])
    assert sum("experts_up_weight[" in k for k in delta) \
        == 3 * TOY["experts_held"]
    assert {"layer0_g_weight", "layer1_g_weight", "layer0_ffn_gate_weight",
            "layer3_ffn_shared_down_weight",
            "layer2_ffn_experts_select_bias"} <= set(delta)
    gaps = sorted(abs(float(delta[k]) - n) / max(n, 1e-3)
                  for k, n in want["delta_norms"].items())
    assert gaps[len(gaps) // 2] < 2e-4 and gaps[-1] < 1e-2, gaps[-3:]
    assert all(n > 0 for n in want["delta_norms"].values())


def test_the_first_gradient_follows_the_reference_by_leaf(fitted):
    """Adam's first moment after ONE step from a zero state is ``(1 - b1)
    g``: every leaf's gradient norm against the reference's autodiff."""
    _, _, _, _, first, want = fitted
    norms = ref.leaf_norms({k: jnp.asarray(v / (1.0 - RECIPE["beta1"]))
                            for k, v in first.items()})
    assert set(norms) == set(want["grad_norms"])
    for name, norm in norms.items():
        assert abs(float(norm) - want["grad_norms"][name]) \
            <= 2e-3 * max(want["grad_norms"][name], 1e-3), name


def program_loss(toy, params, batch):
    """The metric's cross-entropy of one forward pass of the program."""
    it = Ring([batch])
    metric = mx.metric.create("ce")
    mod = mx.mod.Module(get_laguna(**toy), context=mx.cpu(0))
    mod.bind(it.provide_data, it.provide_label, for_training=False)
    # (the selection biases are 0 as drawn: the op's own start)
    mod.set_params({k: mx.nd.array(v) for k, v in params.items()
                    if not k.endswith(ref.STATE)}, {}, allow_missing=True)
    mod.forward(it.next(), is_train=False)
    mod.update_metric(metric, [mx.nd.array(batch[1])])
    return metric.get()[1]


@pytest.fixture(scope="module")
def first_loss():
    batch = toy_batches(1, toy=TOY)[0]
    params0 = {k: np.asarray(v) for k, v in ref.init_params(
        TOY, jax.random.PRNGKey(5)).items()}
    ids, labels = (jnp.asarray(x) for x in batch)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.loss_and_logprob(
            {k: jnp.asarray(v) for k, v in params0.items()}, ids, labels,
            TOY, jnp.arange(4))
    return batch, params0, float(want)


def test_model_loss_follows_the_reference(first_loss):
    batch, params0, want = first_loss
    assert program_loss(TOY, params0, batch) == pytest.approx(want, rel=2e-5)


def widened_gate(params, toy):
    """The per-head gates' weights as the rows ``h * head_dim`` of a gate a
    column, the other rows drawn."""
    out, rng = dict(params), np.random.default_rng(0)
    d = toy["head_dim"]
    for k, v in params.items():
        if k.endswith("_g_weight"):
            wide = rng.standard_normal((v.shape[0] * d, v.shape[1])).astype(
                np.float32) / math.sqrt(v.shape[1])
            wide[::d] = v
            out[k] = wide
    return out


def first_heads(params, toy, heads=6):
    """Every layer's first ``heads`` query heads: their rows of ``W_q`` and
    ``W_g``, their columns of ``W_o``."""
    wide = heads * toy["head_dim"]
    cut = {"q": lambda v: v[:wide], "g": lambda v: v[:heads],
           "o": lambda v: v[:, :wide]}
    out = {}
    for k, v in params.items():
        m = re.fullmatch(r"layer\d+_([qgo])_weight", k)
        out[k] = cut[m.group(1)](v) if m else v
    return out


# each a PROGRAM built with one reading or size wrong, on the reference's
# own weights: (the factory's arguments, what becomes of the weights)
WRONG_PROGRAMS = {
    "window_off": (dict(window=0), None),
    "window_less_one": (dict(window=TOY["window"] - 1), None),
    "gate_off": (dict(attn_gate="none"),
                 lambda p, toy: {k: v for k, v in p.items()
                                 if not k.endswith("_g_weight")}),
    "gate_elementwise": (dict(attn_gate="elementwise"), widened_gate),
    "plain_rotary_on_full_layers": (dict(yarn_factor=1.0), None),
    "attention_factor_1": (dict(yarn_attention_factor=1.0), None),
    "softmax_router": (dict(score_func="softmax"), None),
    "scale_1": (dict(routed_scale=1.0), None),
    "six_heads_on_windowed_layers": (dict(heads_per_layer=[6, 6, 6, 6]),
                                     first_heads),
}


@pytest.mark.parametrize("wrong", sorted(WRONG_PROGRAMS))
def test_a_wrong_reading_fails_the_comparison(first_loss, wrong):
    """Controls that must FAIL: the program with the window off or a key
    short, the gate off or a column wide, plain rotary or no attention
    factor on the full layers, a softmax router, no routed scale, the full
    layers' head count on the windowed ones: each first loss misses the
    reference's by more than five times the sound program's 2e-5 (a key of 16
    fewer moves the mean loss least: 1.4e-4)."""
    batch, params0, want = first_loss
    how, weights = WRONG_PROGRAMS[wrong]
    toy = dict(TOY, **how)
    params = weights(params0, TOY) if weights else params0
    got = program_loss(toy, params, batch)
    assert abs(got - want) > 5 * 2e-5 * want, (got, want)


def test_bfloat16_where_float32_is_stated_fails_the_comparison(monkeypatch,
                                                               first_loss):
    batch, params0, want = first_loss
    monkeypatch.setenv("MXNET_COMPUTE_DTYPE", "bfloat16")
    got = program_loss(TOY, params0, batch)
    assert abs(got - want) > 5 * 2e-5 * want, (got, want)


def test_bad_layers_are_refused():
    with pytest.raises(ValueError, match="not 'full_attention'"):
        get_laguna(**dict(TOY, layer_types=["full_attention", "kda"],
                          heads_per_layer=[6, 8]))
    with pytest.raises(ValueError, match="head counts"):
        get_laguna(**dict(TOY, heads_per_layer=[6, 8]))
    with pytest.raises(ValueError, match="attn_gate"):
        get_laguna(**dict(TOY, attn_gate="row"))
    with pytest.raises(ValueError, match="unknown arguments"):
        ref.config(dict(TOY, n_group=4))


def test_published_defaults_are_the_catalogs():
    """The factory's and the reference's defaults are the published sizes:
    40 layers, full attention at published 0, 4, ..., 36 with 48 query heads
    and windowed layers with 64; the parameter count of the cut the
    configuration states, recounted from the built symbol, and no width of
    the configuration differs from the catalog's row."""
    from mxnet_tpu.models import laguna as model

    assert [i for i, k in enumerate(ref.LAYER_TYPES)
            if k == "full_attention"] == list(range(0, 40, 4))
    assert model.LAYER_TYPES == ref.LAYER_TYPES
    assert model.HEADS_PER_LAYER == ref.config({})["heads_per_layer"]
    assert model.MLP_LAYER_TYPES == ref.config({})["mlp_layer_types"]
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs", CONFIG)) as f:
        config = json.load(f)
    assert config["model"]["args"] == config["reference"]["args"]
    args = config["reference"]["args"]
    assert args["layer_types"] == list(ref.LAYER_TYPES[:5]) \
        == config["layer_types"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    published = dict(ref.DEFAULTS, layer_types=args["layer_types"],
                     vocab=args["vocab"], experts_held=args["experts_held"],
                     bias_update_rate=args["bias_update_rate"])
    assert args["bias_update_rate"] == 0.01 \
        and not ref.DEFAULTS["bias_update_rate"]
    assert ref.config(args) == ref.config(published)
    rope = config["rope_parameters"]
    assert (config["hidden_size"], config["head_dim"],
            config["num_key_value_heads"], config["intermediate_size"],
            config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"],
            config["moe_routed_scaling_factor"], config["rms_norm_eps"]) \
        == (2048, 128, 8, 8192, 512, 512, 8, 512, 2.5, 1e-6)
    c = ref.config(args)
    assert (c["hidden"], c["head_dim"], c["kv_heads"], c["dense_hidden"],
            c["expert_hidden"], c["shared_hidden"], c["top_k"], c["window"],
            c["routed_scale"], c["eps"]) \
        == (2048, 128, 8, 8192, 512, 512, 8, 512, 2.5, 1e-6)
    full, slide = rope["full_attention"], rope["sliding_attention"]
    assert (full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["attention_factor"],
            full["partial_rotary_factor"] * config["head_dim"]) \
        == (c["full_rope_theta"], c["yarn_factor"],
            c["yarn_original_positions"], c["yarn_beta_fast"],
            c["yarn_beta_slow"], c["yarn_attention_factor"],
            c["full_rotary_dim"])
    assert (slide["rope_theta"], slide["partial_rotary_factor"]) \
        == (c["window_rope_theta"], 1)
    assert set(config["reduced"]) == set(config["published"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"}
    # recounted from the built symbol
    net = get_laguna(**config["model"]["args"])
    shape = (config["tokens"]["batch"], config["tokens"]["seq_len"])
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(data=shape, softmax_label=shape)[0]))
    n = sum(int(np.prod(s)) for k, s in shapes.items()
            if k not in ("data", "softmax_label"))
    assert n == 691623936 and "691.62 M" in config["deployment"]
    assert {k: tuple(s) for k, s in shapes.items()
            if k not in ("data", "softmax_label")} \
        == ref.param_shapes(args, states=False)
    # the states the step moves itself: a selection bias an expert layer
    assert set(ref.param_shapes(args)) - set(shapes) == {
        "layer%d_ffn_experts_select_bias" % i for i in (1, 2, 3, 4)} \
        <= set(net.list_auxiliary_states())
    cost = ref.step_cost(args, config["tokens"]["batch"])
    assert config["tokens"] == {"batch": 1, "seq_len": 8192}
    assert cost["params"] == n
    assert set(cost["parts"]) == {
        "attention_proj", "attention_kernel", "attention_window_kernel",
        "dense_ffn", "moe_grouped_matmul", "moe_rest", "lm_head_loss",
        "embed"}
    # the band's useful scores a head: 4,063,488 of the causal 33,558,528
    assert ref.useful_pairs(8192, 512) == 4063488
    assert ref.useful_pairs(8192) == 33558528
    assert cost["parts"]["attention_window_kernel"][0] \
        == 3 * 3 * 4063488 * 64 * 2 * 2 * 128
    assert cost["parts"]["attention_kernel"][0] \
        == 3 * 2 * 33558528 * 48 * 2 * 2 * 128
    # the held experts by the even share: 8,192 x 8 x 32 / 256 rows
    assert cost["parts"]["moe_grouped_matmul"][0] \
        == 3 * 4 * 3 * 2 * 8192 * 2048 * 512


def test_reference_imports_nothing_of_the_program():
    import inspect

    src = inspect.getsource(ref)
    assert "mxnet_tpu" not in src.replace("mxnet_tpu/optimizer.py", "")


# ---------------------------------------------------------------------------
# the toy preset's fused step, from its lowering (tests/test_hlo_gates.py)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def toy_step():
    return lower_language_toy(CONFIG, get_laguna(**TOY),
                              *toy_batches(1, toy=TOY)[0])


def test_the_toy_step_donates_every_master_moment_and_state(toy_step):
    check_state_is_donated(*toy_step)


def test_the_toy_step_takes_bfloat16_products_but_where_named(toy_step):
    check_products_are_bfloat16(*toy_step[:2], {
        # the router's scores, float32 from the normed rows (a choice of
        # experts is discontinuous: ``moe.route``): forward, recomputed,
        # and the two gradients, a layer of experts
        "RoutedExperts": 12,
        # toy widths take ``attend_blockwise``, whose backward pass takes
        # the float32 scores' cotangent against operands widened to it; the
        # cell's heads take the splash kernel (tests/test_cell_lowering.py)
        "attention": 16})
