"""Device observability plane (mxnet_tpu/xprof.py): compile registry
records with real cost/memory analysis on CPU as per-site gauges,
retrace-cause diffs that name the changed argument, op-category FLOP
attribution, the census of a program's instructions by phase, HBM read
at a fence, pre-flight OOM check, and the zero-overhead guarantee for
the fused step (instrumentation must not add dispatches) behind
telemetry's one switch."""
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
    assert r.held_bytes and r.held_bytes > 0  # memory_analysis on CPU
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


# -- a build names itself ---------------------------------------------------

@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent cache in a directory of the test's, writing every
    program however small and quick (the tests run on the CPU without
    one); the process's settings come back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    want = {"jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {name: getattr(jax.config, name) for name in want}
    for name, value in want.items():
        jax.config.update(name, value)
    cc.reset_cache()
    yield tmp_path
    for name, value in before.items():
        jax.config.update(name, value)
    cc.reset_cache()


def _built_again(site):
    """A new jit of the same function: the same module, no executable."""
    def poly(a):
        return jnp.sum(a * a * 3.0 + a)

    f = xprof.jit(poly, site=site, arg_names=("a",))
    f(np.ones((16, 4), np.float32))
    return [r for r in xprof.records() if r.site == site][-1]


def test_record_of_a_built_and_of_a_read_back_program(xp, persistent_cache):
    built = _built_again("t.cache")
    assert built.cache == "built" and built.cache_read_s == 0
    assert built.backend_compile_s > 0
    assert list(persistent_cache.iterdir())          # written
    assert telemetry.peek("compile.t.cache.cache_read", kind="gauge") == 0
    read = _built_again("t.cache")
    assert read.cache == "read" and read.cache_read_s > 0
    # JAX times the backend around the read: what is left of it is small
    assert read.backend_compile_s < read.cache_read_s + 0.05
    for rec in (built, read):
        assert rec.trace_s > 0 and rec.lower_s > 0
        assert rec.trace_s + rec.lower_s + rec.cache_read_s \
            + rec.backend_compile_s <= rec.compile_time_s
        assert rec.kernels == []             # no Pallas kernel: no payload
    assert read.module_sha == built.module_sha and len(read.module_sha) == 64
    assert xprof.diff_builds(built, read) is None
    # of the build's parts the gauges carry the answer alone (a reader's);
    # the rest is the record's, and the summary's below
    gauges = telemetry.snapshot()["compile"]["t"]["cache"]
    assert gauges["cache_read"] == 1
    assert gauges["build_s"] == read.compile_time_s
    assert not {"cache_read_s", "backend_compile_s", "kernels"} & set(gauges)
    assert telemetry.peek("compile.flops") is None   # went in PR 49
    last = xprof.summary()["sites"]["t.cache"]["last"]
    assert (last["cache"], last["module_sha"]) == ("read", read.module_sha)


def test_without_a_persistent_cache_a_build_reads_off(xp):
    rec = _built_again("t.nocache")
    assert rec.cache == "off" and rec.cache_read_s == 0
    assert telemetry.peek("compile.t.nocache.cache_read", kind="gauge") == 0


def test_a_build_inside_a_build_takes_its_own_events():
    with telemetry.jax_build() as outer:
        with telemetry.jax_build() as inner:
            telemetry._on_jax_event("/jax/compilation_cache/cache_hits")
            telemetry._on_jax_duration(
                "/jax/compilation_cache/cache_retrieval_time_sec", 2.0)
        telemetry._on_jax_event(
            "/jax/compilation_cache/compile_requests_use_cache")
        assert outer["cache"] == "off"       # asked is not answered
        telemetry._on_jax_event("/jax/compilation_cache/cache_misses")
        telemetry._on_jax_duration(
            "/jax/core/compile/backend_compile_duration", 30.0)
        telemetry._on_jax_duration("/some/other/event", 1.0)
    assert (inner["cache"], inner["jax.cache_read"]) == ("read", 2.0)
    assert (outer["cache"], outer["jax.cache_read"],
            outer["jax.backend_compile"]) == ("built", 0.0, 30.0)
    telemetry._on_jax_event("/jax/compilation_cache/cache_hits")  # no build


_TWO_KERNELS = """module @jit_step {
  func.func public @main(%arg0: tensor<8xf32>) -> tensor<8xf32> {
    %0 = stablehlo.custom_call @tpu_custom_call(%arg0) {backend_config = \
"{\\22body\\22: \\22FIRST\\22}", kernel_name = "scan_forward", \
operand_layouts = []} : (tensor<8xf32>) -> tensor<8xf32>
    %1 = stablehlo.add %0, %0 : tensor<8xf32>
    %2 = stablehlo.custom_call @tpu_custom_call(%1) {backend_config = \
"{\\22body\\22: \\22SECOND\\22}"} : (tensor<8xf32>) -> tensor<8xf32>
    %3 = stablehlo.custom_call @Sharding(%2) {backend_config = "other"} \
: (tensor<8xf32>) -> tensor<8xf32>
    return %3 : tensor<8xf32>
  }
}
"""


def test_program_identity_cuts_the_payloads_out_of_the_text():
    import hashlib

    a = _TWO_KERNELS.replace("FIRST", "QUJD").replace("SECOND", "REVG")
    b = a.replace("REVG", "R0hJ")           # the second payload differs
    sha_a, kernels_a = xprof.program_identity(a)
    sha_b, kernels_b = xprof.program_identity(b)
    assert sha_a == sha_b
    assert [k[0] for k in kernels_a] == ["scan_forward", "tpu_custom_call"]
    assert kernels_a[0] == kernels_b[0] and kernels_a[1] != kernels_b[1]
    assert kernels_a[0][1] == hashlib.sha256(
        b'{\\22body\\22: \\22QUJD\\22}').hexdigest()
    # another custom call's configuration is module text
    assert xprof.program_identity(a.replace('"other"', '"else"'))[0] != sha_a
    assert xprof.program_identity(a.replace("add", "multiply"))[0] != sha_a
    assert xprof.program_identity("module @jit_f {}") == (
        hashlib.sha256(b"module @jit_f {}").hexdigest(), [])


def _build(**kw):
    base = {"signature": [["params.w", [4, 8], "float32"],
                          ["batch.data", [2, 8], "float32", "dev(0)"]],
            "module_sha": "m0",
            "kernels": [["scan_forward", "a"], ["attention", "b"],
                        ["scan_forward", "c"], ["scan_forward", "d"]]}
    return dict(base, **kw)


def test_diff_builds_names_the_part_that_differs():
    assert xprof.diff_builds(_build(), _build()) is None
    assert xprof.diff_builds(_build(), _build(module_sha="m1")) \
        == "module text outside the kernels"
    other = _build(kernels=[["scan_forward", "a"], ["attention", "B"],
                            ["scan_forward", "C"], ["scan_forward", "D"]])
    assert xprof.diff_builds(_build(), other) == (
        "kernel attention (#1): payload; "
        "kernel scan_forward (#2, #3): payload")
    assert xprof.diff_builds(_build(), _build(kernels=_build()["kernels"][:3])) \
        == "kernels: 4 against 3"
    renamed = _build(kernels=[["scan_forward", "a"], ["attention_v2", "b"]]
                     + _build()["kernels"][2:])
    assert xprof.diff_builds(_build(), renamed) \
        == "kernel attention against attention_v2 (#1)"
    moved = _build(signature=[["params.w", [4, 8], "float32"],
                              ["batch.data", [4, 8], "float32", "dev(0)"]],
                   module_sha="m1")
    assert xprof.diff_builds(_build(), moved) == (
        "arguments: (2,8)float32@dev(0) -> (4,8)float32@dev(0) on "
        "batch.data; module text outside the kernels")
    # records of an older program name nothing they do not hold
    assert xprof.diff_builds({}, {}) is None


def test_diff_builds_takes_records_and_what_a_summary_saved(xp):
    import json

    f = xprof.jit(lambda a: jnp.sum(a * a), site="t.diff",
                  arg_names=("batch.data",))
    f(np.ones((8, 6), np.float32))
    f(np.ones((4, 6), np.float32))
    first, second = [r for r in xprof.records() if r.site == "t.diff"]
    assert first.module_sha != second.module_sha
    want = ("arguments: (8,6)float32 -> (4,6)float32 on batch.data; "
            "module text outside the kernels")
    assert xprof.diff_builds(first, second) == want
    saved = json.loads(json.dumps(xprof.summary()))["sites"]["t.diff"]["last"]
    assert xprof.diff_builds(first.to_dict(), saved) == want
    assert xprof.diff_builds(second, saved) is None


def test_compile_view_shows_what_was_built_and_which_cache_answered(xp):
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from trace_report import render_compile

    rec = _built_again("t.view")
    out = render_compile({"xprof": xprof.summary()})
    header, row = [line.split() for line in out.splitlines()
                   if line.split()[:1] in (["site"], ["t.view"])]
    cell = dict(zip(header, row))
    assert cell["cache"] == "off" and cell["kernels"] == "0"
    assert cell["module"] == rec.module_sha[:12]
    assert float(cell["trace_s"]) > 0 and float(cell["lower_s"]) > 0
    # a record saved by an older program has none of the fields
    old = {"xprof": {"sites": {"fused_step": {
        "compiles": 1, "compile_time_s": 2.0,
        "last": {"compile_time_s": 2.0, "flops": 10.0}}}}}
    assert " - " in render_compile(old)


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


def test_conv_flops_count_the_taps_on_real_elements():
    """The TPU compiler writes a 1x1 convolution's gradient as a
    correlation over a window as large as the image, padded by as much,
    and a strided layer's over a dilated input: the taps on padding and
    between the dilated elements are no work."""
    def shape(*dims):
        return ("bf16", dims, True)

    # weights as the image, the activations as a 5x5 window: a 1x1 conv
    one_by_one = xprof._conv_flops(
        [shape(256, 64, 5, 5)], [shape(64, 64, 1, 1), shape(256, 64, 5, 5)],
        " window={size=5x5 pad=4_4x4_4 rhs_reversal=1x1}, "
        "dim_labels=bf01_oi01->fb01")
    assert one_by_one == 2 * (256 * 64 * 5 * 5) * 64
    # the input gradient of a 3x3 stride-2 layer: 4x4 dilated to 7x7
    dilated = xprof._conv_flops(
        [shape(8, 16, 9, 9)], [shape(8, 32, 4, 4), shape(16, 32, 3, 3)],
        " window={size=3x3 pad=2_2x2_2 lhs_dilate=2x2}, "
        "dim_labels=bf01_oi01->bf01")
    taps = sum((o - 2 + k) % 2 == 0 and 0 <= o - 2 + k <= 6
               for o in range(9) for k in range(3))
    assert taps == 12 and dilated == 2 * 8 * 16 * 32 * taps * taps
    # no padding, no dilation: every tap counts; grouped: the kernel's
    # input-feature dimension is a group's already
    plain = xprof._conv_flops(
        [shape(1, 8, 6, 6)], [shape(1, 8, 8, 8), shape(8, 1, 3, 3)],
        " window={size=3x3}, dim_labels=bf01_oi01->bf01, "
        "feature_group_count=8")
    assert plain == 2 * (8 * 6 * 6) * 9 * 1


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

# what an allocator would answer: ``hbm_stats`` of a chip's device
ASKED = {"live_bytes": 9 << 30, "reserved_bytes": 5 << 30,
         "limit_bytes": 16 << 30, "peak_bytes": 11 << 30,
         "source": "memory_stats"}


def test_hbm_read_at_a_fence_into_gauges(xp):
    """``publish_aux_counters`` reads the first device's memory ONCE and
    sets the ``device.hbm_*`` gauges together, for a module with nothing
    that counts on the device too. Only an allocator's own account
    (``memory_stats()``) becomes a gauge: the CPU has none to ask, and
    what its live arrays hold is not published under an HBM name."""
    mod = Module(_mlp_sym(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (BATCH, DIM))],
             label_shapes=[("softmax_label", (BATCH,))])
    mod.init_params()
    calls = []
    real = xprof.hbm_stats
    asked = ASKED
    try:
        xprof.hbm_stats = lambda dev=None: calls.append(dev) or real(dev)
        mod.publish_aux_counters()
        if real()["source"] == "live_arrays":
            assert "device" not in telemetry.snapshot()
        xprof.hbm_stats = lambda dev=None: calls.append(dev) or asked
        mod.publish_aux_counters()
    finally:
        xprof.hbm_stats = real
    assert calls == [mx.cpu().jax_device()] * 2
    assert telemetry.snapshot()["device"] == {
        "hbm_in_use_bytes": 9 << 30, "hbm_reserved_bytes": 5 << 30,
        "hbm_limit_bytes": 16 << 30}


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


# ---------------------------------------------------------------------------
# one switch: telemetry's
# ---------------------------------------------------------------------------

@pytest.fixture
def tel():
    """Telemetry on, no override: what a ``--trace 1`` run has."""
    prev = xprof._override
    xprof._override = None
    xprof.reset()
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    xprof.reset()
    xprof._override = prev
    telemetry.reset()
    telemetry.disable()


def _deep_sym(layers=4, hidden=32):
    net = sym.Variable("data")
    for i in range(layers):
        net = sym.FullyConnected(net, num_hidden=hidden, name="fc%d" % i)
        net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=CLASSES, name="out")
    return sym.SoftmaxOutput(net, name="softmax")


def _fit(net, optimizer="adam", nbatches=4):
    rng = np.random.RandomState(0)
    X = rng.randn(BATCH * nbatches, DIM).astype(np.float32)
    y = rng.randint(0, CLASSES, (BATCH * nbatches,)).astype(np.float32)
    mod = Module(net, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=BATCH), num_epoch=1,
            optimizer=optimizer, optimizer_params={"learning_rate": 0.01})
    assert mod._fused_step_active
    return mod


def _site_gauges(site="fused_step"):
    node = telemetry.snapshot().get("compile", {})
    for part in site.split("."):
        node = node.get(part, {})
    return node


def test_telemetry_is_the_switch_and_the_step_stays_one_dispatch(
        tel, monkeypatch):
    """With telemetry on the fused step's jit is the instrumented one and
    its record is a set of per-site gauges, set once; the step is the
    same one dispatch and one entry as through the plain ``jax.jit``."""
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    monkeypatch.setattr(xprof, "hbm_stats", lambda dev=None: ASKED)
    assert xprof.enabled()
    xprof.disable()                      # the plain jit, counters on
    _fit(_mlp_sym())
    plain = (telemetry.peek("step.dispatches"),
             telemetry.peek("step.fused_jit_entries", kind="gauge"))
    assert xprof.records() == [] and not _site_gauges()
    xprof._override = None
    telemetry.reset()
    _fit(_mlp_sym())
    assert (telemetry.peek("step.dispatches"),
            telemetry.peek("step.fused_jit_entries", kind="gauge")) == plain
    assert plain == (4, 1)
    assert not telemetry.peek("compile.aot_fallback")
    assert [r.site for r in xprof.records()].count("fused_step") == 1
    g = _site_gauges()
    assert g["held_bytes"] == (g["argument_bytes"] + g["output_bytes"]
                               - g["alias_bytes"] + g["temp_bytes"]
                               + g["generated_code_bytes"]) > 0
    assert g["flops"] > 0 and g["bytes_accessed"] > 0 and g["build_s"] > 0
    assert telemetry.peek("compile.peak_bytes") is None
    # the census ran once, inside the first step's build and under a
    # span of its own
    spans = telemetry.spans()
    census = [sp for sp in spans if sp[0] == "step.census"
              and sp[4] == "step.dispatch"]
    assert len(census) == 1
    builds = [sp for sp in spans if sp[0] == "step.build"]
    assert any(b[2] <= census[0][2] and census[0][2] + census[0][3]
               <= b[2] + b[3] for b in builds)
    # a fence reading, beside it: fit publishes after set-up and at the
    # epoch's end
    assert telemetry.snapshot()["device"]["hbm_reserved_bytes"] == 5 << 30


def test_telemetry_off_plain_jit_and_nothing_published(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    prev = xprof._override
    xprof._override = None
    telemetry.reset()
    try:
        assert not telemetry.enabled() and not xprof.enabled()
        f = xprof.jit(lambda a: a + 1, site="t.off")
        assert not isinstance(f, xprof._InstrumentedJit)
        assert hasattr(f, "lower") and f._cache_size() == 0
        mod = _fit(_mlp_sym())
        mod.publish_aux_counters()
        assert xprof.records() == []
        assert telemetry.snapshot() == {}
    finally:
        xprof._override = prev


def test_steady_state_reaches_the_executable_without_walking_leaves(
        xp, monkeypatch):
    f = xprof.jit(lambda a, b: a * 2.0 + b, site="t.steady",
                  arg_names=("a", "b"))
    walks = []
    real = xprof.leaf_signature
    monkeypatch.setattr(xprof, "leaf_signature",
                        lambda *a, **k: walks.append(1) or real(*a, **k))
    x = np.ones((4, 4), np.float32)
    f(x, x)
    assert len(walks) == 1
    for _ in range(3):
        np.testing.assert_allclose(np.asarray(f(x, x)), 3.0)
    assert len(walks) == 1              # same avals: no walk, no compile
    y = np.ones((2, 4), np.float32)
    np.testing.assert_allclose(np.asarray(f(y, y)), 3.0)
    assert len(walks) == 2              # a new signature: walked, built
    assert f._cache_size() == 2
    assert not telemetry.peek("compile.aot_fallback")
    assert "(4,4)" in xprof.last_retrace_cause()


def test_a_new_placement_is_a_new_signature_not_a_fallback(xp):
    """The kept executable refuses an argument on another device with a
    ``ValueError``, before anything runs or is donated: the walk finds a
    new signature and builds for it, and nothing falls back."""
    import jax

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("one device")
    f = xprof.jit(lambda a: a + 1.0, site="t.placed", arg_names=("a",),
                  donate_argnums=(0,))
    for _ in range(2):
        f(jax.device_put(np.ones((4,), np.float32), devs[0]))
    moved = jax.device_put(np.ones((4,), np.float32), devs[1])
    out = f(moved)
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert out.devices() == {devs[1]}
    assert f._cache_size() == 2 and f._jit._cache_size() == 0
    assert not telemetry.peek("compile.aot_fallback")
    assert "dev(" in xprof.last_retrace_cause()


@pytest.mark.parametrize("error", [TypeError, ValueError])
def test_a_refusal_of_the_same_signature(xp, error):
    """The kept executable raises on arguments of the signature it was
    built for. A ``TypeError`` is an input check stricter than the
    signature: ONE fallback is counted, the plain jit serves this call
    (its donated buffer was never taken) and the later ones, and the
    executable is not tried again. A ``ValueError`` is the call's own:
    raised once, nothing falls back, nothing compiles again."""
    f = xprof.jit(lambda a: a * 3.0, site="t.refused", arg_names=("a",),
                  donate_argnums=(0,))
    x = jnp.ones((4,), jnp.float32)
    f(x + 0)
    sig, built = f._last
    tried = []

    def refuses(*args):
        tried.append(1)
        raise error("refused")

    f._last = (sig, refuses)
    if error is ValueError:
        with pytest.raises(ValueError, match="refused"):
            f(x + 0)
        assert len(tried) == 1 and f._jit._cache_size() == 0
        assert not telemetry.peek("compile.aot_fallback")
        return
    given = x + 0
    np.testing.assert_allclose(np.asarray(f(given)), 3.0)
    assert given.is_deleted()           # donated to the jit that served it
    np.testing.assert_allclose(np.asarray(f(x + 0)), 3.0)
    assert len(tried) == 1 and f._last is None
    assert telemetry.peek("compile.aot_fallback") == 1
    assert f._jit._cache_size() == 1 and f._cache_size() == 1


def test_only_a_site_that_asks_gets_the_census(xp):
    """Another site keeps its op breakdown and gets no census, no
    ``step.census`` span and no ``matrix_flops``."""
    f = xprof.jit(lambda a, b: jnp.dot(a, b), site="t.other",
                  arg_names=("a", "b"))
    f(np.ones((8, 6), np.float32), np.ones((6, 4), np.float32))
    rec = xprof.records()[-1]
    assert rec.op_breakdown["dot"]["flops"] == 2 * 8 * 6 * 4
    assert rec.census is None and rec.matrix_flops is None
    g = _site_gauges("t.other")
    assert g["held_bytes"] > 0 and "census" not in g
    assert "matrix_flops" not in g and "census_loops_once" not in g
    assert not [sp for sp in telemetry.spans() if sp[0] == "step.census"]
    asks = xprof.jit(lambda a, b: jnp.dot(a, b), site="t.asks", census=True)
    asks(np.ones((8, 6), np.float32), np.ones((6, 4), np.float32))
    assert xprof.records()[-1].census_loops_once == 0
    assert _site_gauges("t.asks")["census"]["none"]["ops"] >= 1
    assert "census_loops_once" not in _site_gauges("t.asks")
    assert len([sp for sp in telemetry.spans()
                if sp[0] == "step.census"]) == 1


# ---------------------------------------------------------------------------
# the census by phase
# ---------------------------------------------------------------------------

def _census():
    return _site_gauges().get("census", {})


def test_census_names_the_update_and_adds_up(tel, monkeypatch):
    """A net under Adam: the sets that hold ``update`` move at least the
    update's own arithmetic (weight, two states and gradient of every
    parameter), and the sets' matrix FLOPs are the parser's total."""
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    mod = _fit(_deep_sym(layers=1))
    census = _census()
    assert all(name == "none" or set(name.split("+")) <= set(xprof.PHASES)
               for name in census)
    params = sum(int(np.prod(v.shape))
                 for v in mod.get_params()[0].values())
    update = [row for name, row in census.items()
              if "update" in name.split("+")]
    assert update
    assert sum(row["bytes"] for row in update) >= params * 4 * 4
    assert all(row["ops"] >= 1 for row in census.values())
    rec = [r for r in xprof.records() if r.site == "fused_step"][0]
    matrix = sum(rec.op_breakdown.get(c, {}).get("flops", 0)
                 for c in ("conv", "dot"))
    assert sum(row["flops"] for row in rec.census.values()) == matrix > 0
    assert sum(rec.matrix_flops.values()) == matrix
    assert _site_gauges()["matrix_flops"] == rec.matrix_flops
    # forward 2 N K a product; backward twice that less the first
    # layer's input gradient
    fwd = 2 * BATCH * (DIM * 32 + 32 * CLASSES)
    assert census["fwd"]["flops"] == fwd
    assert matrix == 3 * fwd - 2 * BATCH * DIM * 32


@pytest.mark.parametrize("mirror", [False, True],
                         ids=["kept", "recomputed"])
def test_census_tells_the_recomputed_forward(tel, monkeypatch, mirror):
    """Under ``MXNET_BACKWARD_DO_MIRROR`` the forward pass that
    ``jax.checkpoint`` runs again shows as ``recompute`` (this JAX writes
    ``.../checkpoint/rematted_computation/...`` into ``op_name``);
    without it no set names it."""
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    if mirror:
        monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    _fit(_deep_sym(layers=4))
    names = {p for name in _census() for p in name.split("+")}
    assert ("recompute" in names) == mirror
    assert {"fwd", "bwd", "update"} <= names
    # the products by their own phase (this toy's recomputed pass holds
    # no product: XLA shares the forward's)
    by_phase = _site_gauges()["matrix_flops"]
    assert set(by_phase) <= ({"fwd", "recompute", "bwd"} if mirror
                             else {"fwd", "bwd"})
    assert by_phase["bwd"] > by_phase["fwd"] > 0


_HLO_PHASES = """\
HloModule m

%inner (p: f32[64,64]) -> f32[64,64] {
  %p = f32[64,64]{1,0} parameter(0)
  ROOT %r = f32[64,64]{1,0} maximum(%p, %p), metadata={op_name="jit(step)/bwd/transpose(jvp(fwd))/jvp()/checkpoint/rematted_computation/Activation:a/max"}
}

%fused_update (w: f32[64,64], m: f32[64,64], x: bf16[32,64], g: bf16[32,64]) -> (f32[64,64], f32[64,64]) {
  %w = f32[64,64]{1,0} parameter(0)
  %m = f32[64,64]{1,0} parameter(1)
  %x = bf16[32,64]{1,0} parameter(2)
  %g = bf16[32,64]{1,0:T(8,128)(2,1)S(1)} parameter(3)
  %re = f32[64,64]{1,0} fusion(%w), kind=kLoop, calls=%inner
  %dw = f32[64,64]{1,0} convolution(%x, %g), dim_labels=fb_io->bf, metadata={op_name="jit(step)/bwd/transpose(jvp(FullyConnected:fc))/dot_general"}
  %m2 = f32[64,64]{1,0} add(%m, %dw), metadata={op_name="jit(step)/update/add"}
  %w2 = f32[64,64]{1,0} subtract(%re, %m2), metadata={op_name="jit(step)/update/sub"}
  ROOT %t = (f32[64,64]{1,0}, f32[64,64]{1,0}) tuple(%w2, %m2)
}

%body (c: (s32[], f32[32,64])) -> (s32[], f32[32,64]) {
  %c = (s32[], f32[32,64]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %h = f32[32,64]{1,0} get-tuple-element(%c), index=1
  %h2 = f32[32,64]{1,0} tanh(%h), metadata={op_name="jit(step)/fwd/jvp(Scan:s)/while/body/tanh"}
  ROOT %o = (s32[], f32[32,64]{1,0}) tuple(%i, %h2)
}

%cond (c: (s32[], f32[32,64])) -> pred[] {
  %c = (s32[], f32[32,64]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (w: f32[64,64], m: f32[64,64], x: bf16[32,64], g: bf16[32,64], init: (s32[], f32[32,64])) -> f32[64,64] {
  %w = f32[64,64]{1,0} parameter(0)
  %m = f32[64,64]{1,0} parameter(1)
  %x = bf16[32,64]{1,0} parameter(2)
  %g = bf16[32,64]{1,0:T(8,128)(2,1)S(1)} parameter(3)
  %init = (s32[], f32[32,64]{1,0}) parameter(4)
  %loop = (s32[], f32[32,64]{1,0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(step)/fwd/jvp(Scan:s)/while"}
  %cs = (f32[64,64]{1,0:S(1)}, f32[64,64]{1,0}, u32[]{:S(2)}) copy-start(%w)
  %cd = f32[64,64]{1,0:S(1)} copy-done(%cs)
  %k = f32[64,64]{1,0} custom-call(%cd, %m), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata="{
"xprof_metadata":"{'block_q': 512}"
}}, metadata={op_name="jit(step)/fwd/jvp(Op:k)/pallas_call"}
  %buf = f32[64,64]{1,0} custom-call(), custom_call_target="AllocateBuffer"
  %divide_subtract_fusion = (f32[64,64]{1,0}, f32[64,64]{1,0}) fusion(%w, %m, %x, %g), kind=kOutput, calls=%fused_update, metadata={op_name="jit(step)/update/sub"}
  ROOT %out = f32[64,64]{1,0} get-tuple-element(%divide_subtract_fusion), index=0
}
"""


def test_census_of_a_hand_written_program():
    """A fusion of mixed ``op_name``s reads as the set of its members'
    phases (a fusion inside it included); a loop's body counts once a
    trip; a Pallas call counts its bytes and no FLOPs under its scope,
    which stands on the third line of its text (its metadata has line
    breaks, and a line of it begins with a brace);
    what the compiler keeps on the chip is no HBM traffic; an
    asynchronous pair counts once; a buffer's allocation is no work."""
    census = xprof.hlo_phase_census(_HLO_PHASES)
    f32 = 64 * 64 * 4
    assert census == {
        "fwd": {"ops": 5 + 1, "flops": 0,
                # five trips of tanh in and out; the kernel's HBM
                # operand and result (its other operand is in VMEM)
                "bytes": 5 * 2 * 32 * 64 * 4 + 2 * f32},
        "none": {"ops": 1, "flops": 0, "bytes": f32},
        "recompute+bwd+update": {
            "ops": 1, "flops": 2 * 64 * 64 * 32,
            # w, m, x in; the gradient stays on the chip; w, m out
            "bytes": 4 * f32 + 32 * 64 * 2}}
    bd = xprof.hlo_op_breakdown(_HLO_PHASES)
    assert bd["conv"]["flops"] == 2 * 64 * 64 * 32
    assert bd["elementwise"]["count"] == 5
    assert sum(r["flops"] for r in census.values()) == bd["conv"]["flops"]
    # the product is the backward's own, whatever else its fusion holds
    assert xprof._analyze_hlo(_HLO_PHASES)[2] == {
        "bwd": bd["conv"]["flops"]}
    # a loop whose trip count the text does not say counts once, and
    # the third result says so
    once = xprof._analyze_hlo(_HLO_PHASES.replace("direction=LT",
                                                  "direction=NE"))
    assert once[1]["fwd"]["ops"] == 1 + 1 and once[3] == 1


class _Unreadable:
    """An executable whose text the parser cannot read."""

    def __init__(self, text):
        self._text = text

    def as_text(self):
        if self._text is None:
            raise RuntimeError("no text for this backend")
        return self._text

    def cost_analysis(self):
        return {"flops": 10.0}

    def memory_analysis(self):
        return None

    def runtime_executable(self):
        class _R:
            @staticmethod
            def local_devices():
                return [0]
        return _R()


@pytest.mark.parametrize("text", ["not HLO at all {{{", None],
                         ids=["garbage", "raises"])
def test_unreadable_program_leaves_no_census_and_raises_nothing(
        tel, caplog, text):
    with caplog.at_level("WARNING", logger="mxnet_tpu.xprof"):
        rec = xprof.record_compile("t.unreadable", _Unreadable(text), 0.1)
    assert not rec.census and not rec.op_breakdown
    g = _site_gauges("t.unreadable")
    assert g["flops"] == 10.0 and "census" not in g
    warned = [r for r in caplog.records if "was not read" in r.message]
    assert len(warned) == (1 if text is None else 0)


def test_xprof_ops_off_reads_no_text(tel, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_XPROF_OPS", "0")

    class _NoText(_Unreadable):
        def as_text(self):
            raise AssertionError("the text was read")

    rec = xprof.record_compile("t.notext", _NoText(""), 0.1)
    assert rec.census is None and rec.op_breakdown is None
    assert not [sp for sp in telemetry.spans() if sp[0] == "step.census"]


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


# ---------------------------------------------------------------------------
# the seam between a weight-gradient product and the update (fused_step)
# ---------------------------------------------------------------------------

def _step_text_and_census(monkeypatch, net, force):
    """The compiled step's text, its census and the seam's counters for
    ``net`` under Adam with the seam forced (``None``: by the rule)."""
    import functools

    from mxnet_tpu import fused_step

    texts = []
    analyze = xprof._analyze_hlo
    with monkeypatch.context() as m:
        m.setenv("MXNET_TPU_FUSED_STEP", "1")
        m.setattr(xprof, "_analyze_hlo",
                  lambda text: texts.append(text) or analyze(text))
        if force is not None:
            m.setattr(fused_step, "_plan_update_seam", functools.partial(
                fused_step._plan_update_seam, force=force))
        telemetry.reset()
        xprof.reset()
        _fit(net)
        counts = (telemetry.peek("step.update_seam.apart"),
                  telemetry.peek("step.update_seam.riding"),
                  telemetry.peek("step.update_seam.apart_bytes",
                                 kind="gauge"))
        assert telemetry.peek("step.dispatches") == 4
        assert len(texts) == 1          # one program, read once
        return texts[0], _census(), counts


def test_update_seam_apart_keeps_bwd_and_update_in_sets_of_their_own(
        tel, monkeypatch):
    """Forced apart, no instruction of the compiled step holds both the
    backward pass and the update; the counters are counted once a traced
    program (four steps, one trace); with everything riding, and by the
    rule on a device with no peak, the program's text is one and holds no
    barrier: the parent's."""
    net = _deep_sym(layers=2)
    text, census, counts = _step_text_and_census(monkeypatch, net, "apart")
    n_params = 6                        # three layers' weight and bias
    elements = DIM * 32 + 32 + 32 * 32 + 32 + 32 * CLASSES + CLASSES
    assert counts == (n_params, 0, 4 * elements)
    mixed = [name for name in census
             if {"bwd", "update"} <= set(name.split("+"))]
    assert not mixed, mixed
    assert "update" in census and census["update"]["ops"] >= 1
    riding, _, counts = _step_text_and_census(monkeypatch, net, "riding")
    assert counts == (0, n_params, 0)
    ruled, _, counts = _step_text_and_census(monkeypatch, net, None)
    assert counts == (0, n_params, 0)
    assert ruled == riding != text
    assert "opt-barrier" not in riding
