"""FSDP recipe on the multi-axis ``(dp, fsdp)`` mesh: params and
optimizer state NamedSharding-sharded along ``fsdp``, batch over
``dp x fsdp``, with the all-gather / reduce-scatter exchange emitted by
GSPMD inside the ONE donated fused dispatch. Covers the per-device
byte ratio, bit-identical parity vs dp-only in the exact-arithmetic
regime, one-dispatch/one-compile pinning, the xprof collective
evidence, the escape hatch, and the divisibility gate."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu import telemetry, xprof
from mxnet_tpu.base import MXNetError
from mxnet_tpu.module import Module

# exact-arithmetic regime (see test_sharded_fused.py): linear head,
# integer data, BINARY labels (an 8-wide head grows ~6 mantissa
# bits/step; 0..3 labels would overflow float32 within 8 steps),
# quarter-integer seed weights, power-of-two batch/lr/momentum — every
# product, psum, reduce-scatter partial and update is an exactly
# representable dyadic rational, so dp-only vs (dp, fsdp) parity is
# ``==``, not ``allclose``. HID=8 so fc1 (weight (8, 4), bias (8,))
# actually SHARDS at fsdp=4; a 1-wide head would silently test the
# all-replicated path.
BATCH = 8
DIM = 4
HID = 8


def _lin_sym():
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=HID, name="fc1")
    return mx.sym.LinearRegressionOutput(net, name="lro")


def _synthetic(n, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randint(0, 2, (n, DIM)).astype(np.float32)
    y = rng.randint(0, 2, (n, HID)).astype(np.float32)
    return X, y


def _seed_params(net, seed=9):
    arg_shapes, _, _ = net.infer_shape(data=(BATCH, DIM),
                                       lro_label=(BATCH, HID))
    rng = np.random.RandomState(seed)
    return {name: mx.nd.array(
        (rng.randint(-2, 3, shape) * 0.5).astype(np.float32))
        for name, shape in zip(net.list_arguments(), arg_shapes)
        if name not in ("data", "lro_label")}


def _fit_mesh(monkeypatch, fsdp=0, nbatches=4, num_epoch=2, stream=None,
              momentum=0.5, lr=0.25):
    """One fused training run on all 8 devices: ``fsdp=0`` is the
    dp-only mesh, ``fsdp>1`` sets MXNET_TPU_MESH_FSDP so the group
    builds the ``(dp, fsdp)`` mesh. ``stream`` collects the per-step
    (epoch, nbatch, mse) sequence."""
    if fsdp:
        monkeypatch.setenv("MXNET_TPU_MESH_FSDP", str(fsdp))
    else:
        monkeypatch.delenv("MXNET_TPU_MESH_FSDP", raising=False)
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    net = _lin_sym()
    X, y = _synthetic(BATCH * nbatches)
    data = mx.io.NDArrayIter(X, y, batch_size=BATCH,
                             label_name="lro_label")
    mod = Module(net, context=[mx.cpu(i) for i in range(8)],
                 label_names=("lro_label",))

    def cb(param):
        if stream is not None:
            stream.append(
                (param.epoch, param.nbatch,
                 dict(param.eval_metric.get_name_value())["mse"]))

    mod.fit(data, num_epoch=num_epoch, kvstore="device_sync",
            eval_metric="mse", optimizer="sgd",
            arg_params=_seed_params(net), initializer=None,
            optimizer_params={"learning_rate": lr, "momentum": momentum},
            batch_end_callback=cb)
    return mod


def _bytes_on_dev0(arr):
    import jax

    dev0 = jax.devices()[0]
    shards = getattr(arr, "addressable_shards", None)
    if shards:
        return sum(int(s.data.nbytes) for s in shards
                   if s.device == dev0)
    return int(arr.nbytes)


def _pack_bytes(mod):
    """Params + momentum bytes resident on device 0."""
    import jax

    ex = mod._exec_group.executor
    total = sum(_bytes_on_dev0(ex.arg_dict[n]._data)
                for n in mod._param_names)
    for leaf in jax.tree_util.tree_leaves(mod._updater.states):
        total += _bytes_on_dev0(leaf._data)
    return total


@pytest.fixture
def tel():
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.reset()
    telemetry.disable()


@pytest.mark.multichip
def test_fsdp_mesh_axes_and_param_shardings(monkeypatch):
    """MXNET_TPU_MESH_FSDP=4 on 8 devices builds the dp=2 x fsdp=4
    mesh; divisible params (and their momentum) shard dim 0 along
    ``fsdp``, each device holding a 1/4 shard."""
    mod = _fit_mesh(monkeypatch, fsdp=4, nbatches=2, num_epoch=1)
    mesh = mod._exec_group._mesh
    assert tuple(mesh.axis_names) == ("dp", "fsdp")
    assert int(mesh.shape["dp"]) == 2 and int(mesh.shape["fsdp"]) == 4
    w = mod._exec_group.executor.arg_dict["fc1_weight"]._data
    spec = tuple(w.sharding.spec)
    assert spec and spec[0] == "fsdp", spec
    shard = w.addressable_shards[0].data
    assert shard.shape == (HID // 4, DIM)
    # momentum inherits the weight's sharding (opt-state contract)
    for i, name in enumerate(mod._param_names):
        st = mod._updater.states[i]
        warr = mod._exec_group.executor.arg_dict[name]._data
        assert st._data.sharding == warr.sharding, name


@pytest.mark.multichip
def test_fsdp_param_opt_bytes_quarter_of_replicated(monkeypatch):
    """The point of the recipe: per-device params+opt-state bytes at
    fsdp=4 are 1/4 of the replicated dp-only footprint (every dim 0
    here divides, so the ratio is exact — the acceptance gate allows
    <= 0.35 for models with replicated odd-shaped leaves)."""
    rep = _pack_bytes(_fit_mesh(monkeypatch, nbatches=2, num_epoch=1))
    sh = _pack_bytes(_fit_mesh(monkeypatch, fsdp=4, nbatches=2,
                               num_epoch=1))
    assert rep > 0
    assert sh / rep == pytest.approx(0.25), (sh, rep)


@pytest.mark.multichip
def test_fsdp_bit_identical_to_dp_only(monkeypatch):
    """dp=2 x fsdp=4 == dp=8, bit for bit, through 8 momentum steps:
    the all-gather/reduce-scatter factoring of the exchange is exactly
    the same mean the dp-only psum computes, and the sharded update
    applied per-shard equals the replicated update per-row."""
    s_dp, s_fsdp = [], []
    mod_dp = _fit_mesh(monkeypatch, stream=s_dp)
    mod_fsdp = _fit_mesh(monkeypatch, fsdp=4, stream=s_fsdp)
    assert len(s_dp) == 8
    assert s_dp == s_fsdp
    a, _ = mod_dp.get_params()
    b, _ = mod_fsdp.get_params()
    assert set(a) == set(b)
    for name in sorted(a):
        x, z = a[name].asnumpy(), b[name].asnumpy()
        assert x.dtype == z.dtype
        assert np.array_equal(x, z), (
            "param %s diverged under fsdp (max abs diff %g)"
            % (name, np.abs(x - z).max()))


@pytest.mark.multichip
def test_fsdp_one_dispatch_one_compile(monkeypatch, tel):
    """The whole fsdp step — all-gather, forward, backward,
    reduce-scatter, sharded update — is ONE donated dispatch and ONE
    trace; no fallback reason fires."""
    before_d = tel.peek("step.dispatches") or 0
    before_c = tel.peek("step.fused_recompiles") or 0
    mod = _fit_mesh(monkeypatch, fsdp=4)
    assert mod._fused_step_active
    steps = 8
    assert (tel.peek("step.dispatches") or 0) - before_d == steps
    assert (tel.peek("step.fused_recompiles") or 0) - before_c == 1
    snap = tel.snapshot()
    fallbacks = [k for k in snap.get("step", {})
                 if k.startswith("fused_fallback")]
    assert not fallbacks, fallbacks


@pytest.mark.multichip
def test_fsdp_collective_bucket_has_gather_ops(monkeypatch):
    """The fused executable's HLO carries the fsdp exchange: a nonzero
    collective bucket whose per-opcode sub-buckets include all-gather
    (param gather before use). The CPU backend lowers reduce-scatter
    as all-reduce + dynamic-slice, so the scatter leg shows as
    all-reduce ops here; on TPU it is a literal reduce-scatter."""
    monkeypatch.setenv("MXNET_TPU_XPROF_OPS", "1")
    xprof.enable()
    xprof.reset()
    try:
        _fit_mesh(monkeypatch, fsdp=4, nbatches=2, num_epoch=1)
        rec = (xprof.summary()["sites"].get("fused_step") or {}).get(
            "last") or {}
        bd = rec.get("op_breakdown") or {}
        coll = bd.get("collective")
        assert coll and coll["count"] > 0, bd.keys()
        assert coll["bytes"] > 0
        by_op = coll.get("by_op") or {}
        assert "all-gather" in by_op, by_op.keys()
        assert rec.get("num_devices") == 8
    finally:
        xprof.reset()
        xprof.disable()


@pytest.mark.multichip
def test_fsdp_escape_hatch_keeps_params_replicated(monkeypatch):
    """MXNET_TPU_FSDP_PARAMS=0 keeps the (dp, fsdp) mesh but turns the
    recipe off: params replicate, training still runs fused."""
    monkeypatch.setenv("MXNET_TPU_FSDP_PARAMS", "0")
    mod = _fit_mesh(monkeypatch, fsdp=4, nbatches=2, num_epoch=1)
    assert mod._fused_step_active
    mesh = mod._exec_group._mesh
    assert tuple(mesh.axis_names) == ("dp", "fsdp")
    w = mod._exec_group.executor.arg_dict["fc1_weight"]._data
    assert not any(tuple(w.sharding.spec)), w.sharding
    assert _bytes_on_dev0(w) == w.nbytes


@pytest.mark.multichip
def test_fsdp_indivisible_device_count_raises(monkeypatch):
    """fsdp=3 does not divide 8 devices: the mesh build refuses with a
    message naming the knob, instead of silently dropping devices."""
    monkeypatch.setenv("MXNET_TPU_MESH_FSDP", "3")
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    net = _lin_sym()
    mod = Module(net, context=[mx.cpu(i) for i in range(8)],
                 label_names=("lro_label",))
    with pytest.raises(MXNetError, match="MXNET_TPU_MESH_FSDP"):
        mod.bind(data_shapes=[("data", (BATCH, DIM))],
                 label_shapes=[("lro_label", (BATCH, HID))])


def test_fsdp_spec_helpers():
    """Pure-helper contract: batch over every data axis, params dim-0
    along fsdp only when it divides."""
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel.sharding import (batch_spec, fsdp_param_spec,
                                             make_mesh, mesh_axis_sizes)

    mesh = make_mesh({"dp": 2, "fsdp": 4})
    assert mesh_axis_sizes(mesh) == {"dp": 2, "fsdp": 4}
    assert batch_spec(mesh, 0) == P(("dp", "fsdp"))
    assert fsdp_param_spec((8, 4), mesh) == P("fsdp")
    assert fsdp_param_spec((6, 4), mesh) == P()      # 6 % 4 != 0
    assert fsdp_param_spec((), mesh) == P()          # scalar
    dp_only = make_mesh({"dp": 8})
    assert batch_spec(dp_only, 0) == P("dp")
    assert fsdp_param_spec((8, 4), dp_only) is None  # no fsdp axis
