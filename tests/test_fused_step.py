"""Fused train step (MXNET_TPU_FUSED_STEP=1): gating, numerical parity
with the classic loop, donation safety, dispatch/recompile telemetry,
engine sync semantics, and lazy metric accumulation."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine as eng_mod
from mxnet_tpu import symbol as sym
from mxnet_tpu import telemetry
from mxnet_tpu.fused_step import make_fused_step
from mxnet_tpu.module import Module

BATCH = 8
DIM = 6
CLASSES = 3


def _mlp_sym():
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=CLASSES, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _synthetic(n, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, DIM).astype(np.float32)
    w = rng.randn(DIM, CLASSES)
    y = X.dot(w).argmax(axis=1).astype(np.float32)
    return X, y


def _seed_params(net, seed=3):
    """Deterministic initial params so two fits start bit-identical."""
    arg_shapes, _, _ = net.infer_shape(data=(BATCH, DIM),
                                       softmax_label=(BATCH,))
    rng = np.random.RandomState(seed)
    return {name: mx.nd.array((rng.randn(*shape) * 0.1).astype(np.float32))
            for name, shape in zip(net.list_arguments(), arg_shapes)
            if name not in ("data", "softmax_label")}


def _fit(nbatches, num_epoch=1, fused=False, monkeypatch=None,
         optimizer_params=None):
    if fused:
        monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    else:
        monkeypatch.delenv("MXNET_TPU_FUSED_STEP", raising=False)
    net = _mlp_sym()
    X, y = _synthetic(BATCH * nbatches)
    data = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    mod = Module(net, context=mx.cpu())
    mod.fit(data, num_epoch=num_epoch, optimizer="sgd",
            arg_params=_seed_params(net), initializer=None,
            optimizer_params=optimizer_params
            or {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4})
    assert mod._fused_step_active == fused
    return mod


@pytest.fixture
def tel():
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.reset()
    telemetry.disable()


def test_fused_step_off_by_default(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_FUSED_STEP", raising=False)
    net = _mlp_sym()
    X, y = _synthetic(BATCH * 2)
    data = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    mod = Module(net, context=mx.cpu())
    mod.bind(data.provide_data, data.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    metric = mx.metric.create("acc")
    assert make_fused_step(mod, metric) is None
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    assert make_fused_step(mod, metric) is not None


def test_fused_gate_rejects_custom_update_optimizer(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    net = _mlp_sym()
    X, y = _synthetic(BATCH * 2)
    data = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    mod = Module(net, context=mx.cpu())
    mod.bind(data.provide_data, data.provide_label)
    mod.init_params()
    # "test" overrides update() with eager python math — no traced plan
    mod.init_optimizer(optimizer="test")
    assert make_fused_step(mod, mx.metric.create("acc")) is None


def test_fused_unfused_parity(monkeypatch):
    """Parameter trajectories must be bit-identical after >= 10 batches
    of momentum SGD (same init, same data, same lr schedule)."""
    mod_a = _fit(nbatches=5, num_epoch=2, fused=False,
                 monkeypatch=monkeypatch)
    mod_b = _fit(nbatches=5, num_epoch=2, fused=True,
                 monkeypatch=monkeypatch)
    args_a, _ = mod_a.get_params()
    args_b, _ = mod_b.get_params()
    assert set(args_a) == set(args_b)
    for name in args_a:
        a, b = args_a[name].asnumpy(), args_b[name].asnumpy()
        assert np.array_equal(a, b), \
            "param %s diverged: max |d|=%g" % (name, np.abs(a - b).max())


def test_fused_parity_with_clip_and_scheduler(monkeypatch):
    """Clipping and a per-step lr schedule must not recompile or change
    numerics vs the classic loop."""
    from mxnet_tpu.lr_scheduler import FactorScheduler

    def params():
        return {"learning_rate": 0.05, "momentum": 0.9,
                "clip_gradient": 0.5,
                "lr_scheduler": FactorScheduler(step=3, factor=0.5)}

    mod_a = _fit(nbatches=10, fused=False, monkeypatch=monkeypatch,
                 optimizer_params=params())
    mod_b = _fit(nbatches=10, fused=True, monkeypatch=monkeypatch,
                 optimizer_params=params())
    args_a, _ = mod_a.get_params()
    args_b, _ = mod_b.get_params()
    for name in args_a:
        assert np.array_equal(args_a[name].asnumpy(),
                              args_b[name].asnumpy()), name


def test_fused_one_dispatch_per_batch(tel, monkeypatch):
    """The acceptance criterion: with MXNET_TPU_FUSED_STEP=1 one batch
    issues exactly ONE XLA computation for fwd+bwd+update(+metric)."""
    nbatches = 4
    before = telemetry.peek("step.dispatches") or 0
    _fit(nbatches=nbatches, fused=True, monkeypatch=monkeypatch)
    fused_delta = (telemetry.peek("step.dispatches") or 0) - before
    assert fused_delta == nbatches

    before = telemetry.peek("step.dispatches") or 0
    _fit(nbatches=nbatches, fused=False, monkeypatch=monkeypatch)
    unfused_delta = (telemetry.peek("step.dispatches") or 0) - before
    # classic loop: fwd+bwd, one optimizer group kernel, one metric fold
    assert unfused_delta >= 3 * nbatches


def test_fused_no_retrace_on_same_shapes(tel, monkeypatch):
    """Second and later same-shape batches must reuse the compiled step:
    exactly one fresh trace signature for the whole epoch."""
    before = telemetry.peek("step.fused_recompiles") or 0
    _fit(nbatches=4, fused=True, monkeypatch=monkeypatch)
    assert (telemetry.peek("step.fused_recompiles") or 0) - before == 1


def test_fused_step_donation_safety(monkeypatch):
    """The batch's data/label buffers ride in the NON-donated arg pack:
    they must stay readable (and unchanged) after donating steps."""
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    net = _mlp_sym()
    X, y = _synthetic(BATCH)
    data = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    mod = Module(net, context=mx.cpu())
    mod.bind(data.provide_data, data.provide_label)
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    metric = mx.metric.create("acc")
    fused = mod._fused_train_step(metric)
    assert fused is not None
    batch = next(iter(data))
    before = batch.data[0].asnumpy().copy()
    fused.step(batch, metric)
    fused.step(batch, metric)  # same buffers through a second donation
    np.testing.assert_array_equal(batch.data[0].asnumpy(), before)
    batch.label[0].asnumpy()  # label buffer alive too


def test_naive_engine_skips_block_for_fused_step(monkeypatch):
    class _Ret:
        calls = 0

        def block_until_ready(self):
            self.calls += 1

    monkeypatch.delenv("MXNET_TPU_ENGINE_SYNC", raising=False)
    e = eng_mod.NaiveEngine()
    r = _Ret()
    e.push(lambda: r, prop="fused_step")
    assert r.calls == 0  # donated outputs: no serializing block
    e.push(lambda: r)
    assert r.calls == 1  # default prop still blocks
    monkeypatch.setenv("MXNET_TPU_ENGINE_SYNC", "1")
    e.push(lambda: r, prop="fused_step")
    assert r.calls == 2  # debug switch restores blocking


def test_metric_lazy_device_accumulation():
    """Accuracy.update over NDArrays must not sync to host; get() is the
    only fetch point and matches the numpy computation."""
    rng = np.random.RandomState(11)
    lab_np = rng.randint(0, CLASSES, (BATCH,)).astype(np.float32)
    pred_np = rng.rand(BATCH, CLASSES).astype(np.float32)
    m = mx.metric.create("acc")
    m.update([mx.nd.array(lab_np)], [mx.nd.array(pred_np)])
    assert m.sum_metric == 0.0 and m.num_inst == 0  # host untouched
    assert m._device_acc is not None
    m.update([mx.nd.array(lab_np)], [mx.nd.array(pred_np)])
    _, val = m.get()
    expected = float((pred_np.argmax(axis=1) == lab_np).mean())
    assert val == pytest.approx(expected)
    m.reset()
    assert m._device_acc is None
    assert np.isnan(m.get()[1])


def test_metric_device_folds_match_numpy():
    """Every has_device_fold metric's fold must agree with its own
    eager numpy update path."""
    rng = np.random.RandomState(5)
    cls_lab = rng.randint(0, CLASSES, (BATCH,)).astype(np.float32)
    cls_pred = rng.rand(BATCH, CLASSES).astype(np.float32)
    cls_pred /= cls_pred.sum(axis=1, keepdims=True)
    reg_lab = rng.randn(BATCH).astype(np.float32)
    reg_pred = rng.randn(BATCH, 1).astype(np.float32)
    cases = [(mx.metric.Accuracy(), cls_lab, cls_pred),
             (mx.metric.CrossEntropy(), cls_lab, cls_pred),
             (mx.metric.TopKAccuracy(top_k=2), cls_lab, cls_pred),
             (mx.metric.MSE(), reg_lab, reg_pred),
             (mx.metric.MAE(), reg_lab, reg_pred),
             (mx.metric.RMSE(), reg_lab, reg_pred)]
    for lazy, lab_np, pred_np in cases:
        eager = type(lazy)(top_k=lazy.top_k) \
            if isinstance(lazy, mx.metric.TopKAccuracy) else type(lazy)()
        # instance attr shadows the class flag -> eager numpy path
        eager.has_device_fold = False
        lazy.update([mx.nd.array(lab_np)], [mx.nd.array(pred_np)])
        eager.update([mx.nd.array(lab_np)], [mx.nd.array(pred_np)])
        assert lazy._device_acc is not None
        assert eager._device_acc is None
        assert lazy.get()[1] == pytest.approx(eager.get()[1], rel=1e-5), \
            type(lazy).__name__


def test_fused_metric_matches_host_metric(monkeypatch):
    """The in-step metric fold must produce the same epoch accuracy as
    the classic host-side update."""
    mod_a = _fit(nbatches=6, fused=False, monkeypatch=monkeypatch)
    mod_b = _fit(nbatches=6, fused=True, monkeypatch=monkeypatch)
    X, y = _synthetic(BATCH * 6)
    data = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    sa = mod_a.score(data, "acc")[0][1]
    sb = mod_b.score(data, "acc")[0][1]
    assert sa == pytest.approx(sb)


def test_trace_report_shows_dispatch_columns():
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from trace_report import render

    out = render([{"step": 1, "latency_ms": 10.0, "dominant": "compute",
                   "deltas": {"dispatches": 1, "fused_recompiles": 1}}])
    header = out.splitlines()[2]
    assert "dispatch" in header and "fused_rc" in header


# ---------------------------------------------------------------------------
# the seam between a weight-gradient product and the optimizer's update
# ---------------------------------------------------------------------------

V5E = (197, 819)            # TFLOP/s in bfloat16, GB/s: xprof.CHIP_PEAKS


@pytest.mark.parametrize("rows, shape, n_states, peaks, apart", [
    # a dense feed-forward weight at 8,192 rows under Adam: the MXU binds
    # its product 2.84 times over, 1.01 GB of update
    (8192, (11008, 3840), 2, V5E, True),
    # a head of 16,384 x 2,688: 1.06 GB; no width's divisors are asked
    (8192, (16384, 2688), 2, V5E, True),
    # the feed-forward weight where half the rows are contracted: 1.42,
    # under the margin
    (4096, (11008, 3840), 2, V5E, False),
    # projections of 2,048 x 2,048 (101 MB) and 10,304 x 2,688 (665 MB)
    # at 8,192 rows: under the floor
    (8192, (2048, 2048), 2, V5E, False),
    (8192, (10304, 2688), 2, V5E, False),
    # an expert's weight sees 512 rows: the update's bytes bind
    (512, (1536, 2048), 2, V5E, False),
    # a 3x3 convolution 512 -> 512 at 256 x 14 x 14 positions under SGD
    # with momentum, were it to state its product: the MXU binds it too,
    # but its update moves 38 MB
    (256 * 14 * 14, (512, 512 * 9), 1, V5E, False),
    # a router at 8,192 rows: bound by the MXU, 3 MB of update
    (8192, (64, 2048), 2, V5E, False),
    # a bias, a norm's scale, an embedding: no reader states a product
    (None, (11008,), 2, V5E, False),
    (None, (3840,), 2, V5E, False),
    (None, (12544, 3840), 2, V5E, False),
    # the dense weight where the device reports no peak
    (8192, (11008, 3840), 2, None, False),
], ids=["dense_8192_rows_adam", "head_8192_rows_adam", "dense_4096_rows_adam",
        "dense_101mb_under_floor", "dense_665mb_under_floor",
        "expert_512_rows", "conv3x3_512_sgd_momentum", "router", "bias",
        "norm_scale", "embedding", "no_peak"])
def test_update_seam_rule_on_shapes(rows, shape, n_states, peaks, apart):
    """The rule, on shapes alone: ``rows`` contracted by the product that
    forms the gradient of a float32 weight of ``shape`` (``None``: no
    reader states one) under an optimizer with ``n_states`` states."""
    import math

    from mxnet_tpu.fused_step import _plan_update_seam

    flops = None if rows is None else 2 * rows * math.prod(shape)
    # weight and states in float32, each read and written
    update_bytes = math.prod(shape) * 4 * (1 + n_states) * 2
    plan = _plan_update_seam([flops], [update_bytes], peaks)
    assert plan == (frozenset([0]) if apart else frozenset())
    # the tests' own argument takes either side whatever the shapes say
    assert _plan_update_seam([flops], [update_bytes], peaks,
                             force="apart") == frozenset([0])
    assert _plan_update_seam([flops], [update_bytes], peaks,
                             force="riding") == frozenset()


def test_weight_grad_flops_are_stated_by_the_readers():
    """``FullyConnected`` states ``2 x rows x K x N`` for its weight; a
    bias, a convolution's weight and anything a silent node reads are
    left out, and one argument read twice adds up."""
    from mxnet_tpu.executor import _weight_grad_flops

    net = sym.Variable("data")
    net = sym.Convolution(net, num_filter=4, kernel=(3, 3), name="conv")
    net = sym.Flatten(net)
    shared = sym.Variable("shared_weight")
    net = sym.FullyConnected(data=net, weight=shared, num_hidden=36,
                             no_bias=True, name="fc1")
    net = sym.FullyConnected(data=net, weight=shared, num_hidden=36,
                             no_bias=True, name="fc2")
    net = sym.FullyConnected(net, num_hidden=CLASSES, name="fc3")
    net = sym.SoftmaxOutput(net, name="softmax")
    shapes, _, _ = net.infer_shape(data=(BATCH, 1, 5, 5),
                                   softmax_label=(BATCH,))
    stated = _weight_grad_flops(net, dict(zip(net.list_arguments(), shapes)))
    assert stated == {"shared_weight": 2 * (2 * BATCH * 36 * 36),
                      "fc3_weight": 2 * BATCH * 36 * CLASSES}


def _conv_sym():
    net = sym.Variable("data")
    net = sym.Convolution(net, num_filter=4, kernel=(3, 3), pad=(1, 1),
                          name="conv1")
    net = sym.BatchNorm(net, name="bn1")
    net = sym.Activation(net, act_type="relu")
    net = sym.Flatten(net)
    net = sym.FullyConnected(net, num_hidden=CLASSES, name="fc")
    return sym.SoftmaxOutput(net, name="softmax")


def _seam_fit(net, data_shape, optimizer, optimizer_params, force,
              monkeypatch):
    """Three fused steps from seeded parameters with the seam forced
    (``"apart"`` / ``"riding"``) or by the rule (``None``): parameters,
    auxiliary states and the optimizer's states as numpy."""
    import functools

    from mxnet_tpu import fused_step

    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    if force is not None:
        monkeypatch.setattr(
            fused_step, "_plan_update_seam",
            functools.partial(fused_step._plan_update_seam, force=force))
    rng = np.random.RandomState(7)
    X = rng.randn(BATCH * 3, *data_shape).astype(np.float32)
    y = rng.randint(0, CLASSES, BATCH * 3).astype(np.float32)
    arg_shapes, _, _ = net.infer_shape(data=(BATCH,) + data_shape,
                                       softmax_label=(BATCH,))
    prng = np.random.RandomState(3)
    params = {n: mx.nd.array((prng.randn(*s) * 0.1).astype(np.float32))
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    mod = Module(net, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=BATCH), num_epoch=1,
            optimizer=optimizer, optimizer_params=optimizer_params,
            arg_params=params, initializer=None)
    assert mod._fused_step_active
    args, aux = mod.get_params()
    out = {"arg." + k: v.asnumpy() for k, v in args.items()}
    out.update(("aux." + k, v.asnumpy()) for k, v in aux.items())
    for i, st in mod._updater.states.items():
        for j, s in enumerate(st if isinstance(st, (tuple, list)) else [st]):
            out["state.%d.%d" % (i, j)] = s.asnumpy()
    return out


@pytest.mark.parametrize("optimizer, optimizer_params", [
    ("adam", {"learning_rate": 0.01}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4}),
], ids=["adam", "sgd_momentum"])
@pytest.mark.parametrize("net, data_shape, numwatch, compute_dtype", [
    (_mlp_sym, (DIM,), True, None), (_conv_sym, (1, 5, 5), False, None),
    (_mlp_sym, (DIM,), False, "bfloat16"),
], ids=["mlp_numwatch", "conv", "mlp_bfloat16"])
def test_update_seam_same_results_either_side(tel, monkeypatch, net,
                                              data_shape, numwatch,
                                              compute_dtype, optimizer,
                                              optimizer_params):
    """Apart, riding or by the rule, three steps leave the same
    parameters, auxiliary states and optimizer states, to the bit: the
    seam moves where a gradient is written, not what is computed. Under a
    compute dtype too: the gradient crosses after the cast's transpose,
    as the float32 the update reads, so whatever rounding the compiler
    keeps or drops riding it keeps or drops apart."""
    if numwatch:
        monkeypatch.setenv("MXNET_TPU_NUMWATCH", "1")
    if compute_dtype:
        monkeypatch.setenv("MXNET_COMPUTE_DTYPE", compute_dtype)
    got = {}
    for force in ("apart", "riding", None):
        with monkeypatch.context() as m:
            telemetry.reset()
            got[force] = _seam_fit(net(), data_shape, optimizer,
                                   dict(optimizer_params), force, m)
            n = len(net().list_arguments()) - 2
            apart = telemetry.peek("step.update_seam.apart")
            assert apart == (n if force == "apart" else 0)
            assert telemetry.peek("step.update_seam.riding") == n - apart
            assert telemetry.peek("step.dispatches") == 3
            if force == "apart":
                elements = sum(v.size for k, v in got[force].items()
                               if k.startswith("arg."))
                assert telemetry.peek(
                    "step.update_seam.apart_bytes", kind="gauge") \
                    == elements * 4
    assert got["apart"].keys() == got["riding"].keys() == got[None].keys()
    assert any(k.startswith("state.") for k in got[None])
    for k, want in got["riding"].items():
        np.testing.assert_array_equal(got[None][k], want, err_msg=k)
        np.testing.assert_array_equal(got["apart"][k], want, err_msg=k)


# ---------------------------------------------------------------------------
# the step's outputs are complete before the update that overwrites what
# they were computed from
# ---------------------------------------------------------------------------

def test_outputs_nearest_parameters_by_graph():
    """From each output back to the first node that reads a trained
    parameter: a classifier's last ``FullyConnected`` (weight and bias,
    past the loss node that reads only the label), a language model's head
    (past the label's reshape), a weight shared with an earlier node
    once."""
    from mxnet_tpu.fused_step import _outputs_nearest_parameters

    net = _mlp_sym()
    params = set(net.list_arguments()) - {"data", "softmax_label"}
    assert _outputs_nearest_parameters(net, params) \
        == {"fc2_weight", "fc2_bias"}
    x = sym.Embedding(data=sym.Variable("data"), input_dim=32,
                      output_dim=8, name="embed")
    x = sym.Reshape(data=x, shape=(-1, 8))
    shared = sym.Variable("embed_weight")
    x = sym.FullyConnected(data=sym.RMSNorm(data=x, name="norm"),
                           weight=shared, num_hidden=32, no_bias=True,
                           name="head")
    lm = sym.SoftmaxOutput(
        data=x, label=sym.Reshape(data=sym.Variable("softmax_label"),
                                  shape=(-1,)), name="softmax")
    params = set(lm.list_arguments()) - {"data", "softmax_label"}
    assert _outputs_nearest_parameters(lm, params) == {"embed_weight"}
    # a parameter nobody trains stops no path
    assert _outputs_nearest_parameters(lm, {"norm_gamma"}) == {"norm_gamma"}


@pytest.mark.parametrize("net, data_shape, numwatch, compute_dtype", [
    (_mlp_sym, (DIM,), True, None), (_conv_sym, (1, 5, 5), False, None),
    (_mlp_sym, (DIM,), False, "bfloat16"),
], ids=["mlp_numwatch", "conv", "mlp_bfloat16"])
def test_outputs_before_update_same_results(tel, monkeypatch, net,
                                            data_shape, numwatch,
                                            compute_dtype):
    """Tied to the outputs or not, three steps leave the same parameters,
    auxiliary states and optimizer states, to the bit: the barrier says
    when the last layer's update may begin, not what it computes. Below
    the floor (every program of these tests) nothing is tied."""
    from mxnet_tpu import fused_step

    if numwatch:
        monkeypatch.setenv("MXNET_TPU_NUMWATCH", "1")
    if compute_dtype:
        monkeypatch.setenv("MXNET_COMPUTE_DTYPE", compute_dtype)
    got = {}
    for floor in (0, None):
        with monkeypatch.context() as m:
            telemetry.reset()
            if floor is not None:
                m.setattr(fused_step, "_OUTPUTS_FLOOR_BYTES", floor)
            got[floor] = _seam_fit(net(), data_shape, "adam",
                                   {"learning_rate": 0.01}, None, m)
            assert telemetry.peek("step.outputs_before_update") \
                == (2 if floor == 0 else None)
            assert telemetry.peek("step.dispatches") == 3
    assert got[0].keys() == got[None].keys()
    for k, want in got[None].items():
        np.testing.assert_array_equal(got[0][k], want, err_msg=k)
