"""Sharded fused step (device_sync kvstore): in-jit GSPMD gradient
exchange. dp=8 vs dp=1 bit-identical parity, one-dispatch and
no-retrace regressions under NamedSharding, donation safety, fused
default-on under device_sync, and the xprof collective bucket."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu import telemetry, xprof
from mxnet_tpu.module import Module

# exact-arithmetic regime so dp=8 mean-psum reduction order cannot
# perturb bits: integer-valued data/labels, quarter-integer weights,
# power-of-two batch/lr/rescale — every product, partial sum, psum and
# update is an exactly-representable dyadic rational in float32
BATCH = 16          # global; 2 rows per shard at dp=8
DIM = 4
HID = 8


def _reg_sym():
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=HID, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=1, name="fc2")
    return mx.sym.LinearRegressionOutput(net, name="lro")


def _synthetic(n, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randint(-3, 4, (n, DIM)).astype(np.float32)
    y = rng.randint(-3, 4, (n, 1)).astype(np.float32)
    return X, y


def _seed_params(net, seed=9, batch=BATCH):
    arg_shapes, _, _ = net.infer_shape(data=(batch, DIM),
                                       lro_label=(batch, 1))
    rng = np.random.RandomState(seed)
    return {name: mx.nd.array(
        (rng.randint(-2, 3, shape) * 0.5).astype(np.float32))
        for name, shape in zip(net.list_arguments(), arg_shapes)
        if name not in ("data", "lro_label")}


# single-layer head for the bit-parity tests: backward through a hidden
# layer multiplies two current-weight quantities (mantissa doubles per
# step, float32 rounds by step 2), while the linear head's gradient
# x^T(pred-label) is linear in the weights — mantissa grows ~5 bits per
# step and K=4 steps stay exactly representable
LBATCH = 8          # 1 row per shard at dp=8; mean divides by 2^3


def _lin_sym():
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=1, name="fc1")
    return mx.sym.LinearRegressionOutput(net, name="lro")


def _synthetic_lin(n, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randint(0, 2, (n, DIM)).astype(np.float32)
    y = rng.randint(0, 4, (n, 1)).astype(np.float32)
    return X, y


def _fit_dp(dp, nbatches=6, num_epoch=2, monkeypatch=None, fused_env="1",
            linear=False, lr=0.5):
    if fused_env is None:
        monkeypatch.delenv("MXNET_TPU_FUSED_STEP", raising=False)
    else:
        monkeypatch.setenv("MXNET_TPU_FUSED_STEP", fused_env)
    batch = LBATCH if linear else BATCH
    net = _lin_sym() if linear else _reg_sym()
    X, y = (_synthetic_lin if linear else _synthetic)(batch * nbatches)
    data = mx.io.NDArrayIter(X, y, batch_size=batch, label_name="lro_label")
    mod = Module(net, context=[mx.cpu(i) for i in range(dp)],
                 label_names=("lro_label",))
    mod.fit(data, num_epoch=num_epoch, kvstore="device_sync",
            eval_metric="mse", optimizer="sgd",
            arg_params=_seed_params(net, batch=batch), initializer=None,
            optimizer_params={"learning_rate": lr})
    return mod


@pytest.fixture
def tel():
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.reset()
    telemetry.disable()


@pytest.mark.multichip
def test_sharded_fused_bit_identical_to_single_device(monkeypatch):
    """dp=8 GSPMD mean-psum == dp=1 fused step, bit for bit, after K
    steps inside the exact-arithmetic window: the in-jit gradient
    exchange is exactly a mean reduce, not approximately equivalent.

    A linear head keeps every quantity a dyadic rational (~5 mantissa
    bits added per step), so K=4 steps are exactly representable in
    float32 and reduction order (1-row shards + psum vs one 8-row
    reduce) cannot perturb bits. A wrong rescale or a sum-not-mean
    reduce would diverge at step 1 by far more than rounding."""
    mod1 = _fit_dp(1, nbatches=4, num_epoch=1, monkeypatch=monkeypatch,
                   linear=True)
    mod8 = _fit_dp(8, nbatches=4, num_epoch=1, monkeypatch=monkeypatch,
                   linear=True)
    assert mod1._fused_step_active and mod8._fused_step_active
    args1, _ = mod1.get_params()
    args8, _ = mod8.get_params()
    assert set(args1) == set(args8)
    for name in sorted(args1):
        a, b = args1[name].asnumpy(), args8[name].asnumpy()
        assert a.dtype == b.dtype
        assert np.array_equal(a, b), (
            "param %s diverged under sharding (max abs diff %g)"
            % (name, np.abs(a - b).max()))
    # and training actually moved the params
    init = _seed_params(_lin_sym(), batch=LBATCH)
    assert any(not np.array_equal(args8[n].asnumpy(), init[n].asnumpy())
               for n in init)


@pytest.mark.multichip
def test_sharded_fused_tracks_single_device_long_run(monkeypatch):
    """Past the exact window only float non-associativity separates the
    two reductions: after 12 steps the params still agree to rounding
    noise."""
    mod1 = _fit_dp(1, nbatches=6, num_epoch=2, monkeypatch=monkeypatch,
                   lr=0.0625)
    mod8 = _fit_dp(8, nbatches=6, num_epoch=2, monkeypatch=monkeypatch,
                   lr=0.0625)
    args1, _ = mod1.get_params()
    args8, _ = mod8.get_params()
    for name in sorted(args1):
        np.testing.assert_allclose(
            args1[name].asnumpy(), args8[name].asnumpy(),
            rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.multichip
def test_sharded_fused_one_dispatch_per_batch(tel, monkeypatch):
    """dispatches_per_step stays 1.0 under NamedSharding: the gradient
    exchange costs zero extra dispatches."""
    nbatches, epochs = 6, 2
    before = telemetry.peek("step.dispatches") or 0
    _fit_dp(8, nbatches=nbatches, num_epoch=epochs, monkeypatch=monkeypatch)
    delta = (telemetry.peek("step.dispatches") or 0) - before
    assert delta / float(nbatches * epochs) == 1.0


@pytest.mark.multichip
def test_sharded_fused_no_retrace_across_batches(tel, monkeypatch):
    """One trace serves every batch and epoch: sharded inputs arrive
    with a stable aval+sharding signature on the staged feed path."""
    before = telemetry.peek("step.fused_recompiles") or 0
    _fit_dp(8, nbatches=5, num_epoch=3, monkeypatch=monkeypatch)
    assert (telemetry.peek("step.fused_recompiles") or 0) - before == 1


@pytest.mark.multichip
def test_sharded_fused_seam_apart_follows_the_pinned_collective(
        tel, monkeypatch):
    """The update's seam under the mesh, forced apart: every gradient
    crosses its barrier as the value the sharding constraint pinned (the
    collective stays between backward and update), the step is one jit
    entry, and the parameters are the riding step's, bit for bit."""
    import functools

    import jax

    from mxnet_tpu import fused_step

    riding = _fit_dp(8, nbatches=4, num_epoch=1, monkeypatch=monkeypatch,
                     linear=True)
    pinned, crossed = [], []
    pin, barrier = jax.lax.with_sharding_constraint, \
        jax.lax.optimization_barrier

    def spy_pin(x, s):
        pinned.append(pin(x, s))
        return pinned[-1]

    def spy_barrier(x):
        crossed.append(x)
        return barrier(x)

    monkeypatch.setattr(jax.lax, "with_sharding_constraint", spy_pin)
    monkeypatch.setattr(jax.lax, "optimization_barrier", spy_barrier)
    monkeypatch.setattr(
        fused_step, "_plan_update_seam",
        functools.partial(fused_step._plan_update_seam, force="apart"))
    telemetry.reset()
    apart = _fit_dp(8, nbatches=4, num_epoch=1, monkeypatch=monkeypatch,
                    linear=True)
    n_params = len(apart._param_names)
    assert telemetry.peek("step.update_seam.apart") == n_params
    assert telemetry.peek("step.update_seam.riding") == 0
    assert len(crossed) == n_params
    assert all(any(c is p for p in pinned) for c in crossed)
    assert telemetry.peek("step.fused_jit_entries", kind="gauge") == 1
    assert telemetry.peek("step.fused_recompiles") == 1
    args_r, _ = riding.get_params()
    args_a, _ = apart.get_params()
    for name in sorted(args_r):
        assert np.array_equal(args_r[name].asnumpy(),
                              args_a[name].asnumpy()), name


@pytest.mark.multichip
def test_sharded_fused_donation_safety(monkeypatch):
    """Donated params/opt-state buffers stay safe under NamedSharding
    across many steps — a use-after-donate raises inside jax, and the
    surviving params must be finite and real."""
    mod = _fit_dp(8, nbatches=4, num_epoch=4, monkeypatch=monkeypatch,
                  lr=0.03125)
    args, _ = mod.get_params()
    for name, arr in args.items():
        assert np.isfinite(arr.asnumpy()).all(), name


@pytest.mark.multichip
def test_device_sync_defaults_fused_on(monkeypatch):
    """device_sync flips kvstore.fused_step_compatible: the fused path
    engages with MXNET_TPU_FUSED_STEP unset, and the
    MXNET_TPU_DEVICE_SYNC_FUSED=0 escape hatch restores the classic
    loop."""
    monkeypatch.delenv("MXNET_TPU_DEVICE_SYNC_FUSED", raising=False)
    mod = _fit_dp(8, nbatches=3, num_epoch=1,
                  monkeypatch=monkeypatch, fused_env=None)
    assert mod._fused_step_active
    monkeypatch.setenv("MXNET_TPU_DEVICE_SYNC_FUSED", "0")
    mod = _fit_dp(8, nbatches=3, num_epoch=1,
                  monkeypatch=monkeypatch, fused_env=None)
    assert not mod._fused_step_active


@pytest.mark.multichip
def test_sharded_step_has_collective_bucket(monkeypatch):
    """The xprof op-category breakdown of the sharded fused executable
    reports a nonzero collective bucket — the gradient all-reduce is
    visibly inside the one dispatch."""
    monkeypatch.setenv("MXNET_TPU_XPROF_OPS", "1")
    xprof.enable()
    xprof.reset()
    try:
        _fit_dp(8, nbatches=3, num_epoch=1, monkeypatch=monkeypatch)
        xp = xprof.summary()
        last = (xp["sites"].get("fused_step") or {}).get("last") or {}
        bd = last.get("op_breakdown") or {}
        coll = bd.get("collective")
        assert coll, "sharded fused step compiled without collective ops"
        assert coll.get("count", 0) > 0
        assert coll.get("bytes", 0) > 0
        assert last.get("num_devices") == 8
    finally:
        xprof.reset()
        xprof.disable()
