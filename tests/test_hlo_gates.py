"""Off-chip HLO regression gates (round-4 verdict #1a).

Tier-1 runs on the CPU, so a perf regression would otherwise be invisible
until the next on-chip run. These gates assert compiled-program properties
of the train step the image cells run (the fused step ``Module.fit``
builds under ``MXNET_TPU_FUSED_STEP=1``: forward, backward, momentum SGD
and the metric's fold in one donated jit) for both image nets at small
sizes: flop ratios, buffer donation, bf16 conv layouts, transpose counts,
from ``jit.lower(...).compile()`` on whatever backend CI has. They are
proxies for the on-chip numbers the reference publishes
(/root/reference/example/image-classification/README.md:202-257): the
exact TPU schedules differ, but the regressions these catch (double
compute, lost donation, f32 convs sneaking back, layout thrash in the
traced graph) show up on any backend.
"""
import inspect
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.fused_step import make_fused_step

BATCH, IMAGE, NUM_CLASSES = 8, 32, 16
CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")
# the image cells' nets at small sizes, each beside its cell's
# configuration: the file's ``env`` and ``fit`` blocks are what the cell
# trains under (read here, nothing of the benchmark imported)
NETS = {
    "resnet50": ("resnet50_b256_bf16_fused.json", lambda: models.get_resnet50(
        num_classes=NUM_CLASSES, small_input=True)),
    "inception_bn": ("inception_bn_b256_bf16.json",
                     lambda: models.get_inception_bn_28_small(
                         num_classes=NUM_CLASSES)),
}


def _cost(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}


def lower_fused_step(net, batch, env, fit, **init):
    """The fused step of ``net`` as ``Module.fit`` builds it under the
    environment ``env`` and the recipe ``fit`` (a configuration's blocks of
    those names; ``init`` goes to ``init_params``), through
    ``fused_step.make_fused_step`` with ``batch`` marshalled, LOWERED from
    its one jit (nothing compiled, nothing run). Returns the step, the
    lowering and the leaves the step donates: parameters, auxiliary
    states, optimizer states and the metric's accumulators."""
    with pytest.MonkeyPatch.context() as patch:
        for name, value in env.items():
            patch.setenv(name, value)
        mod = mx.mod.Module(net, context=mx.cpu(0))
        mod.bind(data_shapes=[("data", batch.data[0].shape)],
                 label_shapes=[("softmax_label", batch.label[0].shape)])
        mod.init_params(**init)
        mod.init_optimizer(kvstore=fit["kvstore"],
                           optimizer=fit["optimizer"],
                           optimizer_params=dict(fit["optimizer_params"]))
        step = make_fused_step(mod, mx.metric.create(fit["eval_metric"]))
        assert step is not None and step._fold_leaves is not None
        # the dispatch closure holds the argument packs of the step's
        # one jit, in the order it calls it
        do, _, _ = step._marshal(batch)
        (jit_step,) = step._jit_cache.values()
        held = inspect.getclosurevars(do).nonlocals
        lowered = jit_step.lower(*(held[n] for n in (
            "p_vals", "o_vals", "aux_vals", "st_vals", "sv_mats", "accs",
            "key")))
    donated = jax.tree_util.tree_leaves(
        (held["p_vals"], held["aux_vals"], held["st_vals"], held["accs"]))
    return step, lowered, donated


def products(lowered):
    """``(place, node, lhs dtype, rhs dtype)`` of every ``dot_general`` of a
    lowering, from its locations: ``place`` is the operator and ``node``
    the graph node whose scope the product was traced under
    (``fwd/jvp(FullyConnected:lm_head)/...``); a function lowered on its
    own (a ``custom_vjp``'s, ``lax.map``'s body) starts the name stack
    anew, and its products are placed by the file of ``mxnet_tpu/ops``
    their call site is in (``seq``, ``attention``), ``node`` None."""
    text = lowered.as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))

    def place(ref):
        tag = re.match(r'"[^"]*?(\w+):(\w+)', locs[ref])
        if tag:
            return tag.groups()
        seen = set()
        while ref not in seen:
            seen.add(ref)
            where = re.search(r"mxnet_tpu/ops/(\w+)\.py", locs.get(ref, ""))
            if where:
                return where.group(1), None
            ref = (re.findall(r"#loc\d+", locs.get(ref, "")) or [ref])[0]
        raise AssertionError("a product placed nowhere: %s" % locs[ref])

    el = r"tensor<(?:[\d?]+x)*(\w+)>"
    return [place(m.group(3)) + m.group(1, 2) for m in re.finditer(
        r"stablehlo\.dot_general.*: \(%s, %s\).*loc\((#loc\d+)\)"
        % (el, el), text)]


def lower_language_toy(config, net, ids, labels):
    """:func:`lower_fused_step` of a language model's toy preset under the
    ``env`` and ``fit`` blocks of its cell's configuration (Adam over
    float32 masters, bfloat16 compute, recomputation), one batch of int32
    ids and next-token labels."""
    with open(os.path.join(CONFIGS, config)) as f:
        config = json.load(f)
    batch = mx.io.DataBatch([mx.nd.array(ids, dtype=np.int32)],
                            [mx.nd.array(labels, dtype=np.int32)], pad=0)
    return lower_fused_step(net, batch, config["env"], config["fit"],
                            initializer=mx.init.Xavier())


def check_state_is_donated(step, lowered, donated):
    """Every parameter (under bfloat16 compute the float32 master), Adam's
    two moments of each, every auxiliary state and the metric's two
    accumulators are aliased inputs of the step: a donation lost is that
    state twice in HBM on the chip, where the language cells hold 13.2-15.4
    of 16.9 GB at a fence, and nothing on the CPU."""
    ex = step._executor
    masters = [ex.arg_arrays[i]._data for i in step._p_arg_idx]
    assert masters and all(m.dtype == jnp.float32 for m in masters)
    assert len(donated) == 3 * len(masters) + len(ex.aux_arrays) + 2
    aliased = lowered.as_text().count("tf.aliasing_output")
    assert aliased >= len(donated), (
        "%d of the step's %d parameters, moments and states are aliased "
        "inputs" % (aliased, len(donated)))


def check_products_are_bfloat16(step, lowered, float32_allowed):
    """Under bfloat16 compute every ``FullyConnected`` node's products
    (forward, both gradients; the head's among them) take two bfloat16
    operands, no product mixes the two, and the products that take two
    float32 operands are no more, place by place, than ``float32_allowed``
    names (each with its reason beside it)."""
    found = products(lowered)
    nodes = [n["name"]
             for n in json.loads(step._module.symbol.tojson())["nodes"]
             if n["op"] == "FullyConnected"]
    assert "lm_head" in nodes
    for node in nodes:
        mine = [(a, b) for place, n, a, b in found
                if (place, n) == ("FullyConnected", node)]
        assert len(mine) >= 3 and set(mine) == {("bf16", "bf16")}, (node, mine)
    assert {(a, b) for _, _, a, b in found} <= {("bf16", "bf16"),
                                                 ("f32", "f32")}
    float32 = {}
    for place, _, a, _ in found:
        if a == "f32":
            float32[place] = float32.get(place, 0) + 1
    assert set(float32) <= set(float32_allowed), float32
    assert all(n <= float32_allowed[p] for p, n in float32.items()), float32


@pytest.fixture(scope="module", params=sorted(NETS))
def train_lowering(request):
    """The fused step of one image net as its cell's ``fit`` builds it,
    lowered and compiled: shared by all gates of the net."""
    config, build = NETS[request.param]
    with open(os.path.join(CONFIGS, config)) as f:
        config = json.load(f)
    rng = np.random.RandomState(0)
    batch = mx.io.DataBatch(
        data=[mx.nd.array(rng.rand(BATCH, 3, IMAGE, IMAGE)
                          .astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, NUM_CLASSES, BATCH)
                           .astype(np.float32))])
    net = build()
    step, lowered, donated = lower_fused_step(
        net, batch, config["env"], config["fit"],
        initializer=mx.init.Xavier(rnd_type="uniform", factor_type="avg",
                                   magnitude=3))
    compiled = lowered.compile()
    ex = step._executor
    params = {ex.arg_names[i]: ex.arg_arrays[i]._data
              for i in step._p_arg_idx}
    data = {ex.arg_names[i]: ex.arg_arrays[i]._data
            for i in step._o_arg_idx}
    return {"net": net, "params": params, "data": data,
            "aux": [a._data for a in ex.aux_arrays], "donated": donated,
            "compiled": compiled, "mlir": lowered.as_text()}


def test_train_step_donates_params_and_aux(train_lowering):
    """Every parameter, every aux buffer, every momentum state and the
    metric's accumulators must be donated into the step — losing donation
    costs a transient 2x of that state in HBM on chip (round-4 verdict
    weak #3; the language cells hold 13-15 of 16.9 GB at a fence)."""
    n_donatable = len(train_lowering["donated"])
    assert n_donatable >= 2 * len(train_lowering["params"]) \
        + len(train_lowering["aux"])
    aliased = train_lowering["mlir"].count("tf.aliasing_output")
    assert aliased >= n_donatable, (
        "expected >= %d donated buffers in the train step, lowering "
        "records %d" % (n_donatable, aliased))


@pytest.fixture(scope="module")
def fwd_compiled(train_lowering):
    """Inference-forward compile of the same net, the yardstick for the
    train-step flop/byte ratios (same backend, so backend-specific
    layout-copy inflation cancels out of the ratios)."""
    from mxnet_tpu.executor import make_graph_eval
    net = train_lowering["net"]
    params, data, aux = (train_lowering["params"], train_lowering["data"],
                         train_lowering["aux"])
    eval_graph, _ = make_graph_eval(net)
    arg_names = net.list_arguments()

    def fwd(params, data, aux):
        args = [params[n] if n in params else data[n] for n in arg_names]
        outs, _ = eval_graph(args, aux, None, False)
        return outs[0]

    return jax.jit(fwd).lower(params, data, aux).compile()


def test_train_step_flops_ratio(train_lowering, fwd_compiled):
    """Train-step flops must stay ~3x the inference forward (fwd + bwd-
    data + bwd-weights). A silent double-compute regression (lost remat
    boundary, duplicated subgraph, monitor fetch leaking into the hot
    step) breaks the upper bound; dropping the backward breaks the
    lower. The fused step reads 3.26 (ResNet-50) and 3.15 (Inception-BN):
    momentum SGD and the metric's fold add nothing a bound would see."""
    train_flops = float(_cost(train_lowering["compiled"]).get("flops", 0.0))
    assert train_flops > 0, "cost_analysis returned no flop count"
    fwd_flops = float(_cost(fwd_compiled).get("flops", 0.0))
    assert fwd_flops > 0
    ratio = train_flops / fwd_flops
    assert 2.0 <= ratio <= 4.2, (
        "train/forward flop ratio %.2f out of [2.0, 4.2] "
        "(train=%.3e fwd=%.3e)" % (ratio, train_flops, fwd_flops))


def test_train_step_convs_run_bf16(train_lowering):
    """Under compute_dtype=bfloat16 every convolution must consume bf16
    operands — one f32 conv halves MXU throughput for that op on chip.
    Asserted on the lowered stablehlo (the traced graph, which this
    framework controls): backends without native bf16 convs (CPU) upcast
    at compile time, but on TPU the traced dtype is what the MXU sees."""
    convs = [ln for ln in train_lowering["mlir"].splitlines()
             if "stablehlo.convolution" in ln]
    assert len(convs) >= 100, (
        "expected the fused fwd+bwd conv stack (~3 a Convolution node: 158 "
        "for ResNet-50's 53, 146 for Inception-BN's), found %d" % len(convs))
    f32_convs = [ln.strip() for ln in convs
                 if re.search(r"xf32>", ln.split("->")[0])]
    assert not f32_convs, (
        "%d convolutions traced with f32 operands under bf16 compute:\n%s"
        % (len(f32_convs), "\n".join(c[:200] for c in f32_convs[:5])))


def test_train_step_transpose_bound(train_lowering):
    """Layout-thrash gate on the traced graph: the step traces 3
    transposes total (measured 2026-07-31; the compiled count is backend
    layout policy — CPU normalizes every conv to its preferred layout —
    so the gate pins what the framework itself emits). A jump past the
    bound means a new explicit layout conversion entered the hot path
    (the round-2..4 NHWC work was exactly about these)."""
    transposes = len([ln for ln in train_lowering["mlir"].splitlines()
                      if "stablehlo.transpose" in ln])
    assert transposes <= 16, (
        "%d traced transposes in the train step (bound 16, baseline 3)"
        % transposes)


def test_train_step_bytes_accessed_ratio(train_lowering, fwd_compiled):
    """HBM-traffic gate: train-step bytes accessed stays within 8x the
    inference forward's (fwd+bwd re-reads activations ~3x; backend
    layout-copy inflation affects both sides equally). Catches a
    materialized all-internals fetch or a lost fusion leaking whole
    activation maps to memory. The fused step reads 7.17 (ResNet-50) and
    6.86 (Inception-BN), the float32 masters, their momentum and the
    bfloat16 casts of the weights among the bytes."""
    touched = float(_cost(train_lowering["compiled"])
                    .get("bytes accessed", 0.0))
    fwd_touched = float(_cost(fwd_compiled).get("bytes accessed", 0.0))
    if touched <= 0 or fwd_touched <= 0:
        pytest.skip("backend reports no bytes-accessed estimate")
    ratio = touched / fwd_touched
    assert ratio <= 8.0, (
        "train step touches %.1fx the forward's bytes (bound 8x; "
        "train=%.1f MB fwd=%.1f MB)"
        % (ratio, touched / 1e6, fwd_touched / 1e6))


def test_executor_fwd_bwd_donates_aux():
    """The Module/fit path (Executor._fwd_bwd) must donate the aux (BN
    stat) buffers: backward() always writes aux_out back, so the old
    buffers are dead and XLA should reuse their HBM."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym

    data = sym.Variable("data")
    net = sym.Convolution(data, num_filter=8, kernel=(3, 3), name="conv")
    net = sym.BatchNorm(net, name="bn")
    net = sym.FullyConnected(sym.Flatten(net), num_hidden=4, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")

    ex = net.simple_bind(mx.cpu(), data=(2, 3, 8, 8))
    args = [a._data for a in ex.arg_arrays]
    aux = [a._data for a in ex.aux_arrays]
    assert aux, "test symbol must carry BN aux states"
    key = jax.random.PRNGKey(0)
    outs_spec, _ = jax.eval_shape(ex._fwd_train, args, aux, key)
    heads = [jnp.ones(s.shape, s.dtype) for s in outs_spec]
    mlir = ex._get_fwd_bwd(False).lower(args, aux, key, heads).as_text()
    assert mlir.count("tf.aliasing_output") >= len(aux), (
        "executor fwd+bwd lowering donates %d buffers, expected the %d "
        "aux states" % (mlir.count("tf.aliasing_output"), len(aux)))


def test_optimizer_update_donates_and_matches_eager():
    """The fused update kernels donate weight+state (in-place in HBM, the
    XLA form of the reference's in-place optimizer kernels) and keep the
    reference math: sgd-momentum checked against a hand-rolled eager
    step."""
    from mxnet_tpu.optimizer import _apply_update

    w = jnp.asarray(np.random.RandomState(0).randn(64, 32), jnp.float32)
    g = jnp.asarray(np.random.RandomState(1).randn(64, 32), jnp.float32)
    m = jnp.zeros_like(w)
    lr, wd, mom, rescale = 0.1, 1e-4, 0.9, 1.0

    expect_g = g * rescale + wd * w
    expect_m = mom * m - lr * expect_g
    expect_w = w + expect_m

    new_w, (new_m,) = _apply_update("sgd", w, g, (m,),
                                    (rescale, lr, wd, mom), clipped=False)
    np.testing.assert_allclose(np.asarray(new_w), np.asarray(expect_w),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(new_m), np.asarray(expect_m),
                               rtol=1e-6)
    # donation consumed the inputs (default engine runs closures inline,
    # so _donation_ok() held and the old buffers must be gone)
    for old in (w, m):
        with pytest.raises(RuntimeError):
            _ = np.asarray(old)


def test_optimizer_update_scalar_change_reuses_compile():
    """An LRScheduler changes lr every step; the update kernel must not
    retrace per value (scalars ride in a traced vector)."""
    from mxnet_tpu.optimizer import _JIT_UPDATES, _apply_update

    w = jnp.ones((16,), jnp.float32)
    g = jnp.ones((16,), jnp.float32)
    _apply_update("sgd", w, g, (), (1.0, 0.1, 0.0, 0.0), clipped=False)
    key = [k for k in _JIT_UPDATES if k[0] == "sgd" and k[1] == 0][0]
    fn = _JIT_UPDATES[key]
    before = fn._cache_size()
    for lr in (0.09, 0.05, 0.01):
        w2 = jnp.ones((16,), jnp.float32)
        _apply_update("sgd", w2, g, (), (1.0, lr, 0.0, 0.0), clipped=False)
    assert fn._cache_size() == before, (
        "update kernel retraced on an lr change: cache grew %d -> %d"
        % (before, fn._cache_size()))


def _lstm_lowering(seq, batch=4, vocab=200, hidden=16, layers=2):
    from mxnet_tpu import sym
    from mxnet_tpu.parallel import build_sgd_train_step

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    embed = sym.Embedding(data=data, input_dim=vocab, output_dim=hidden,
                          name="embed")
    rnn = sym.RNN(data=embed, state=sym.Variable("rnn_state"),
                  state_cell=sym.Variable("rnn_state_cell"),
                  parameters=sym.Variable("rnn_parameters"),
                  state_size=hidden, num_layers=layers, mode="lstm",
                  name="rnn")
    pred = sym.FullyConnected(sym.Reshape(rnn, shape=(-1, hidden)),
                              num_hidden=vocab, name="pred")
    net = sym.SoftmaxOutput(
        data=sym.Reshape(pred, shape=(seq, -1, vocab)), label=label,
        preserve_shape=True, name="softmax")
    rng = np.random.RandomState(0)
    arg_shapes, _, _ = net.infer_shape(data=(seq, batch))
    params, feed = {}, {}
    for name, shape in zip(net.list_arguments(), arg_shapes):
        if name == "data":
            feed[name] = jnp.asarray(rng.randint(0, vocab, shape),
                                     jnp.int32)
        elif name == "softmax_label":
            feed[name] = jnp.asarray(rng.randint(0, vocab, shape),
                                     jnp.float32)
        elif "state" in name:
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            params[name] = jnp.asarray(rng.randn(*shape) * 0.05,
                                       jnp.float32)
    step, _ = build_sgd_train_step(net, ["data"], ["softmax_label"],
                                   lr=0.1)
    return jax.jit(step, donate_argnums=(0, 2)).lower(
        params, feed, [], jax.random.PRNGKey(0)).as_text()


def test_lstm_train_step_stays_scan_based():
    """RNN regression gate: the fused-scan LSTM must trace as
    lax.while/scan loops whose GRAPH SIZE is independent of sequence
    length. An unrolling regression (a Python loop sneaking into the
    RNN op, a scan falling back to per-step tracing) multiplies compile
    time and program size by bptt length — the exact failure the
    reference avoided with its fused cudnn_rnn kernel."""
    short = _lstm_lowering(seq=12)
    longer = _lstm_lowering(seq=24)
    n_while = sum(1 for ln in short.splitlines()
                  if "stablehlo.while" in ln)
    assert n_while >= 2, (
        "LSTM train step traced %d while loops — the scan structure "
        "is gone" % n_while)
    n_dots = sum(1 for ln in short.splitlines() if "stablehlo.dot" in ln)
    assert n_dots <= 40, (
        "%d dot ops in the LSTM step (baseline 15): per-timestep "
        "matmuls are no longer inside the scan" % n_dots)
    assert len(short.splitlines()) == len(longer.splitlines()), (
        "LSTM trace size depends on sequence length (%d lines at "
        "bptt=12 vs %d at bptt=24) — the scan has unrolled"
        % (len(short.splitlines()), len(longer.splitlines())))
