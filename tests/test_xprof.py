"""Device observability plane (mxnet_tpu/xprof.py): compile registry
records with real cost/memory analysis on CPU, retrace-cause diffs that
name the changed argument, op-category FLOP attribution, HBM watermark,
pre-flight OOM check, and the zero-overhead guarantee for the fused
step (instrumentation must not add dispatches)."""
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import symbol as sym
from mxnet_tpu import telemetry, xprof
from mxnet_tpu.base import MXNetError
from mxnet_tpu.module import Module

BATCH = 8
DIM = 6
CLASSES = 3


@pytest.fixture
def xp():
    prev = xprof._override
    xprof.enable()
    xprof.reset()
    telemetry.reset()
    telemetry.enable()
    yield xprof
    xprof.reset()
    xprof._override = prev
    telemetry.reset()
    telemetry.disable()


# ---------------------------------------------------------------------------
# compile registry
# ---------------------------------------------------------------------------

def test_compile_record_nonzero_flops_on_cpu(xp):
    f = xprof.jit(lambda a, b: jnp.dot(a, b) + 1.0, site="t.matmul",
                  arg_names=("a", "b"))
    a = np.ones((8, 6), np.float32)
    b = np.ones((6, 4), np.float32)
    np.testing.assert_allclose(np.asarray(f(a, b)), a.dot(b) + 1.0)
    recs = [r for r in xprof.records() if r.site == "t.matmul"]
    assert len(recs) == 1
    r = recs[0]
    assert r.compile_time_s > 0
    assert r.flops and r.flops > 0          # cost_analysis on CPU
    assert r.peak_bytes and r.peak_bytes > 0  # memory_analysis on CPU
    assert r.retrace_cause is None  # first compile: nothing to diff
    assert telemetry.peek("compile.count") == 1
    assert (telemetry.peek("compile.time_ms", kind="hist_sum") or 0) > 0


def test_same_shapes_reuse_executable(xp):
    f = xprof.jit(lambda a: a * 2.0, site="t.reuse", arg_names=("a",))
    x = np.ones((4, 4), np.float32)
    f(x)
    f(np.zeros((4, 4), np.float32))  # same avals: no second compile
    assert len([r for r in xprof.records() if r.site == "t.reuse"]) == 1


def test_retrace_cause_names_changed_aval(xp):
    f = xprof.jit(lambda a: jnp.sum(a * a), site="t.retrace",
                  arg_names=("batch.data",))
    f(np.ones((8, 6), np.float32))
    f(np.ones((4, 6), np.float32))
    recs = [r for r in xprof.records() if r.site == "t.retrace"]
    assert len(recs) == 2
    cause = recs[1].retrace_cause
    assert "batch.data" in cause
    assert "(8,6)" in cause and "(4,6)" in cause
    assert "batch.data" in (xprof.last_retrace_cause() or "")


def test_recompile_detector_event_carries_cause(xp):
    from mxnet_tpu import tracing

    f = xprof.jit(lambda a: a + 1.0, site="t.cause", arg_names=("x",))
    f(np.ones((8,), np.float32))
    f(np.ones((4,), np.float32))  # seeds _last_cause with "on x"
    det = tracing.RecompileDetector(warmup=0)
    ev = det.check({"step": 5, "latency_ms": 80.0,
                    "deltas": {"compiles": 1}})
    assert ev is not None and ev["compiles"] == 1
    assert "on x" in ev.get("cause", "")


def test_tracing_marks_compile_dominant(xp):
    from mxnet_tpu import tracing

    fields = [f for f, _m, _k in tracing.DELTA_SOURCES]
    assert "compiles" in fields and "compile_ms" in fields
    assert tracing.StepTrace._dominant({"compiles": 1}, 50.0) == "compile"


# ---------------------------------------------------------------------------
# op-category attribution
# ---------------------------------------------------------------------------

_HLO = """\
HloModule m

ENTRY %main (a: f32[8,6], b: f32[6,4], i: f32[1,3,8,8], k: f32[4,3,3,3]) -> (f32[8,4], f32[1,4,6,6]) {
  %a = f32[8,6]{1,0} parameter(0)
  %b = f32[6,4]{1,0} parameter(1)
  %i = f32[1,3,8,8]{3,2,1,0} parameter(2)
  %k = f32[4,3,3,3]{3,2,1,0} parameter(3)
  %dot = f32[8,4]{1,0} dot(f32[8,6]{1,0} %a, f32[6,4]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %conv = f32[1,4,6,6]{3,2,1,0} convolution(f32[1,3,8,8]{3,2,1,0} %i, f32[4,3,3,3]{3,2,1,0} %k), window={size=3x3}, dim_labels=bf01_oi01->bf01, feature_group_count=1
  ROOT %out = (f32[8,4], f32[1,4,6,6]) tuple(%dot, %conv)
}
"""


def test_op_breakdown_analytic_model_and_sum():
    bd = xprof.hlo_op_breakdown(_HLO)
    # dot (8,6)x(6,4): 2*8*4*6; conv out (1,4,6,6), 3x3 kernel, Cin=3
    assert bd["dot"]["flops"] == 2 * 8 * 4 * 6
    assert bd["conv"]["flops"] == 2 * (4 * 6 * 6) * 9 * 3
    total = sum(v["flops"] for v in bd.values())
    assert total == bd["dot"]["flops"] + bd["conv"]["flops"]
    for cat in bd:
        assert cat in xprof.CATEGORIES


def test_real_executable_breakdown_sums_to_total(xp):
    f = xprof.jit(lambda a, b: jnp.tanh(jnp.dot(a, b)), site="t.ops",
                  arg_names=("a", "b"))
    f(np.ones((8, 6), np.float32), np.ones((6, 4), np.float32))
    r = [r for r in xprof.records() if r.site == "t.ops"][0]
    assert r.op_breakdown, "MXNET_TPU_XPROF_OPS default-on"
    total = sum(v["flops"] for v in r.op_breakdown.values())
    assert total > 0
    assert r.op_breakdown.get("dot", {}).get("flops", 0) > 0
    assert set(r.op_breakdown) <= set(xprof.CATEGORIES)


def test_analyze_roofline_classification():
    # v5e ridge = 197e12 / 819e9 ≈ 240 FLOP/B
    hi = xprof.analyze(1e12, 1e9, step_time_s=0.01, device_kind="v5e")
    assert hi["bound"] == "compute"
    assert hi["analytic_mfu_pct"] > 0
    lo = xprof.analyze(1e9, 1e9, device_kind="v5e")
    assert lo["bound"] == "bandwidth"
    # the CPU is an explicit "no peak": no MFU field at all, never a 0.0
    # that reads as a measurement
    cpu = xprof.analyze(1e9, 1e9, step_time_s=0.1)
    assert "analytic_mfu_pct" not in cpu
    assert cpu["peak_tflops"] is None and cpu["bound"] == "unknown"
    assert cpu["achieved_tflops"] > 0


def test_peak_lookup_one_table_unknown_accelerator_raises():
    assert xprof.chip_peaks("TPU v5 lite") == (197, 819)
    assert xprof.chip_peak_tflops("TPU v5 lite") == 197
    assert xprof.chip_hbm_gbps("TPU v5e") == 819
    assert xprof.chip_peaks("cpu") is None
    for kind in ("TPU v9 mystery", "NVIDIA H100", "", None):
        with pytest.raises(MXNetError, match="no published peak"):
            xprof.chip_peaks(kind)


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------

def test_hbm_watermark_monotone_within_step(xp):
    wm = xprof.HbmWatermark()
    wm.sample()
    peaks = [wm.peak]
    keep = []
    for i in range(3):
        keep.append(jnp.ones((64, 64), jnp.float32) * i)
        wm.sample()
        peaks.append(wm.peak)
    assert all(b >= a for a, b in zip(peaks, peaks[1:]))
    assert peaks[-1] > 0
    stats = xprof.hbm_stats()
    assert stats["source"] in ("memory_stats", "live_arrays")
    del keep


def test_preflight_refuses_impossible_config(xp):
    with pytest.raises(MXNetError, match="pre-flight OOM"):
        xprof.preflight_check(10 << 30, limit_bytes=1 << 30,
                              what="test step")
    # fits: returns the headroom
    assert xprof.preflight_check(1 << 20, limit_bytes=1 << 30) > 0
    # no limit known (CPU): advisory no-op
    assert xprof.preflight_check(10 << 30, limit_bytes=None) is None


# ---------------------------------------------------------------------------
# fused-step regression: observability must be free
# ---------------------------------------------------------------------------

def _mlp_sym():
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=CLASSES, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def test_fused_step_instrumented_still_one_dispatch(xp, monkeypatch):
    """The AOT wrapper dispatches the cached executable directly — with
    xprof ON, dispatches-per-step must stay exactly 1.0 and the compile
    registry must hold the fused_step record with real FLOPs."""
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    nbatches = 4
    rng = np.random.RandomState(0)
    X = rng.randn(BATCH * nbatches, DIM).astype(np.float32)
    y = rng.randint(0, CLASSES, (BATCH * nbatches,)).astype(np.float32)
    data = mx.io.NDArrayIter(X, y, batch_size=BATCH)
    mod = Module(_mlp_sym(), context=mx.cpu())
    before = telemetry.peek("step.dispatches") or 0
    mod.fit(data, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    assert mod._fused_step_active
    delta = (telemetry.peek("step.dispatches") or 0) - before
    assert delta / float(nbatches) == 1.0
    recs = [r for r in xprof.records() if r.site == "fused_step"]
    assert len(recs) == 1
    assert recs[0].flops and recs[0].flops > 0
    # the fused retrace diff speaks executor language: batch.* / params.*
    sig_names = [n for n, _a in recs[0].signature]
    assert any(n.startswith("batch.") for n in sig_names)
    assert any(n.startswith("params.") for n in sig_names)


def test_disabled_xprof_records_nothing():
    prev = xprof._override
    try:
        xprof.disable()
        xprof.reset()
        f = xprof.jit(lambda a: a + 1, site="t.off")
        f(np.ones((2,), np.float32))
        assert xprof.records() == []
    finally:
        xprof._override = prev
        xprof.reset()
