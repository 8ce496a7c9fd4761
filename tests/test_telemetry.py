"""Unified telemetry subsystem: counters/gauges/histograms, spans,
exporters, and the framework instrumentation that reports through them
(engine, io, executor, kvstore, profiler.StepTimer)."""
import json
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError


@pytest.fixture(autouse=True)
def _isolated_telemetry():
    """Each test starts with a clean, enabled registry and leaves the
    process-global state the way the suite expects (disabled, empty)."""
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.reset()
    telemetry.disable()


# -- primitive semantics -------------------------------------------------

def test_counter_semantics():
    telemetry.inc("t.c")
    telemetry.inc("t.c", 5)
    assert telemetry.counter("t.c").value == 6
    # registry returns the same object per name
    assert telemetry.counter("t.c") is telemetry.counter("t.c")


def test_gauge_last_write_wins():
    telemetry.set_gauge("t.g", 1.0)
    telemetry.set_gauge("t.g", 42.5)
    assert telemetry.gauge("t.g").value == 42.5


def test_histogram_summary_and_bound():
    h = telemetry.histogram("t.h", capacity=8)
    for v in range(100):
        telemetry.observe("t.h", float(v))
    ex = h.export()
    assert ex["count"] == 100
    assert ex["sum"] == sum(range(100))
    assert ex["min"] == 0.0 and ex["max"] == 99.0
    # ring is bounded: percentile sample holds only the last `capacity`
    assert len(h._ring) == 8
    assert ex["p50"] >= 92.0  # drawn from the most recent 8 samples


def test_metric_type_clash_raises():
    telemetry.inc("t.kind")
    with pytest.raises(MXNetError):
        telemetry.gauge("t.kind")


def test_snapshot_nesting_and_collision():
    telemetry.inc("a.b.c", 3)
    telemetry.set_gauge("a.b", 1.5)  # both leaf and prefix
    snap = telemetry.snapshot()
    assert snap["a"]["b"]["c"] == 3
    assert snap["a"]["b"]["_value"] == 1.5


# -- disabled mode -------------------------------------------------------

def test_disabled_mode_records_nothing():
    telemetry.disable()
    telemetry.inc("off.c")
    telemetry.set_gauge("off.g", 1.0)
    telemetry.observe("off.h", 1.0)
    with telemetry.span("off.span"):
        pass
    assert telemetry.snapshot() == {}
    assert telemetry.spans() == []


# -- spans ---------------------------------------------------------------

def test_span_records_interval_and_histogram():
    with telemetry.span("work"):
        pass
    (name, tid, _t0, dur, parent, step) = telemetry.spans()[-1]
    assert name == "work"
    assert tid == threading.get_ident()
    assert dur >= 0.0
    assert parent is None and step == 0
    snap = telemetry.snapshot()
    assert snap["span"]["work_ms"]["count"] == 1


def test_write_chrome_trace(tmp_path):
    with telemetry.span("step"):
        pass
    with telemetry.span("step"):
        pass
    path = str(tmp_path / "trace.json")
    n = telemetry.write_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert n == len(evs)
    xs = [ev for ev in evs if ev["ph"] == "X"]
    assert len(xs) == 2
    for ev in xs:
        assert ev["name"] == "step"
        assert ev["ts"] > 0 and ev["dur"] >= 0
    # ph="M" metadata names this process's lanes for merged traces
    metas = [ev for ev in evs if ev["ph"] == "M"]
    names = {ev["name"] for ev in metas}
    assert "process_name" in names and "thread_name" in names
    import os
    assert all(ev["pid"] == os.getpid() for ev in metas)


def test_write_chrome_trace_extra_events(tmp_path):
    with telemetry.span("host"):
        pass
    extra = [{"name": "remote", "ph": "X", "pid": 999, "tid": 1,
              "ts": 1.0, "dur": 2.0}]
    path = str(tmp_path / "trace.json")
    telemetry.write_chrome_trace(path, extra_events=extra)
    with open(path) as f:
        doc = json.load(f)
    assert any(ev.get("name") == "remote" and ev.get("pid") == 999
               for ev in doc["traceEvents"])


# -- concurrency ---------------------------------------------------------

def test_concurrent_increments_from_engine_workers():
    """Increments racing from ThreadedEngine worker threads must not
    lose updates."""
    from mxnet_tpu import engine as eng

    e = eng.ThreadedEngine(num_workers=4)
    n_ops = 200
    for _ in range(n_ops):
        e.push(lambda: telemetry.inc("race.c"),
               const_vars=(), mutable_vars=(e.new_variable(),))
    e.wait_for_all()
    assert telemetry.counter("race.c").value == n_ops
    # the engine's own instrumentation counted every push and dispatch
    assert telemetry.counter("engine.push").value >= n_ops
    assert telemetry.counter("engine.dispatch").value >= n_ops
    # queue-wait histogram saw the same ops
    qw = telemetry.histogram("engine.queue_wait_ms")
    assert qw.count >= n_ops


# -- exporters -----------------------------------------------------------

def test_dump_jsonl_round_trip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    telemetry.inc("j.c", 7)
    telemetry.dump_jsonl(path)
    telemetry.inc("j.c", 1)
    telemetry.dump_jsonl(path, extra={"note": "second"})
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert [r["step"] for r in recs] == [1, 2]
    assert recs[0]["telemetry"]["j"]["c"] == 7
    assert recs[1]["telemetry"]["j"]["c"] == 8
    assert recs[1]["note"] == "second"
    assert all("ts" in r for r in recs)


def test_step_timer_feeds_telemetry(tmp_path):
    path = str(tmp_path / "steps.jsonl")
    timer = mx.profiler.StepTimer(jsonl_path=path)
    n_steps = 3
    for _ in range(n_steps):
        with timer:
            pass
    assert telemetry.counter("profiler.steps").value == n_steps
    assert telemetry.histogram("profiler.step_ms").count == n_steps
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert len(recs) == n_steps
    assert all("step_ms" in r for r in recs)


def test_speedometer_emits_gauge():
    class _Param:
        epoch, nbatch, eval_metric = 0, 0, None

    sp = mx.callback.Speedometer(batch_size=4, frequent=2)
    p = _Param()
    sp(p)            # init tick
    p.nbatch = 2
    sp(p)            # frequent boundary -> emits
    assert telemetry.gauge("train.samples_per_sec").value > 0
    assert telemetry.counter("train.batches").value == 2


# -- end to end ----------------------------------------------------------

def test_module_fit_populates_counters(tmp_path):
    """A small Module.fit must leave nonzero engine/io/executor counters
    and dump_jsonl must produce one parseable record per step."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    x = np.random.RandomState(0).rand(20, 4).astype(np.float32)
    y = (np.arange(20) % 8).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=4)
    mod = mx.mod.Module(net, data_names=["data"],
                        label_names=["softmax_label"])
    path = str(tmp_path / "fit.jsonl")

    class _PerStep:
        def __call__(self, param):
            telemetry.dump_jsonl(path)

    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            batch_end_callback=_PerStep())
    snap = telemetry.snapshot()
    assert snap["engine"]["dispatch"] > 0
    assert snap["engine"]["push"] > 0
    assert snap["io"]["batches"] >= 5
    assert snap["executor"]["forward"] >= 5
    assert snap["executor"]["backward"] >= 5
    assert snap["executor"]["jit_build"] >= 1
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert len(recs) == 5  # 20 samples / batch 4 = 5 steps
    assert recs[-1]["telemetry"]["executor"]["forward"] >= 5


def test_kvstore_counters():
    kv = mx.kv.create("local")
    a = mx.nd.ones((4, 4))
    kv.init(0, a)
    kv.push(0, mx.nd.ones((4, 4)))
    out = mx.nd.zeros((4, 4))
    kv.pull(0, out=out)
    snap = telemetry.snapshot()
    assert snap["kvstore"]["push"] >= 1
    assert snap["kvstore"]["pull"] >= 1
    assert snap["kvstore"]["push_bytes"] >= 4 * 4 * 4
    assert snap["kvstore"]["pull_bytes"] >= 4 * 4 * 4


# -- bucketed export + fleet merge (obswatch federation core) ------------

def test_histogram_bucket_export_cumulative():
    h = telemetry.Histogram("t.ms", bounds=(1.0, 5.0, 10.0))
    for v in (0.5, 0.7, 3.0, 20.0):
        h.observe(v)
    ex = h.export()
    assert ex["count"] == 4
    assert ex["buckets"]["bounds"] == [1.0, 5.0, 10.0]
    # cumulative le counts; the +Inf bucket is implicit (== count)
    assert ex["buckets"]["counts"] == [2, 3, 3]
    empty = telemetry.Histogram("t.empty", bounds=(1.0,)).export()
    assert empty == {"count": 0,
                     "buckets": {"bounds": [1.0], "counts": [0]}}


def test_bucket_quantile_interpolation():
    buckets = {"bounds": [10.0, 20.0], "counts": [10, 20]}
    # rank 10 of 20 sits at the top of the first bucket
    assert telemetry.bucket_quantile(buckets, 20, 0.5) == 10.0
    # rank 15 is halfway through the 10..20 bucket
    assert telemetry.bucket_quantile(buckets, 20, 0.75) == \
        pytest.approx(15.0)
    # ranks past the last finite bound clamp to the observed max
    assert telemetry.bucket_quantile(
        {"bounds": [10.0], "counts": [0]}, 5, 0.5, hi=42.0) == 42.0
    assert telemetry.bucket_quantile({}, 0, 0.5) is None


def test_merge_snapshots_sums_and_recurses():
    a = {"engine": {"push": 3, "dispatch": 1}, "io": {"wait_ms": 1.5}}
    b = {"engine": {"push": 4}, "io": {"wait_ms": 0.5}, "extra": 1}
    merged = telemetry.merge_snapshots([a, b])
    assert merged["engine"] == {"push": 7, "dispatch": 1}
    assert merged["io"]["wait_ms"] == pytest.approx(2.0)
    assert merged["extra"] == 1
    # inputs are never mutated
    assert a["engine"]["push"] == 3 and b["engine"]["push"] == 4


def test_merge_snapshots_histograms_bucket_wise():
    ha = telemetry.Histogram("a.ms", bounds=(1.0, 10.0))
    hb = telemetry.Histogram("b.ms", bounds=(1.0, 10.0))
    for v in (0.5, 2.0):
        ha.observe(v)
    for v in (3.0, 50.0):
        hb.observe(v)
    merged = telemetry.merge_snapshots(
        [{"lat": ha.export(include_sample=True)},
         {"lat": hb.export(include_sample=True)}])["lat"]
    assert merged["count"] == 4
    assert merged["buckets"]["counts"] == [1, 3]
    assert merged["min"] == 0.5 and merged["max"] == 50.0
    assert merged["sum"] == pytest.approx(55.5)
    # exact percentiles from the concatenated samples
    assert merged["sample"] == [0.5, 2.0, 3.0, 50.0]
    assert merged["p50"] == 3.0
    # without samples, percentiles interpolate from the merged buckets
    no_sample = telemetry.merge_snapshots(
        [{"lat": ha.export()}, {"lat": hb.export()}])["lat"]
    assert "sample" not in no_sample
    assert 1.0 <= no_sample["p50"] <= 10.0


def test_merge_snapshots_conflicting_bounds_raise():
    ha = telemetry.Histogram("a.ms", bounds=(1.0, 10.0))
    hb = telemetry.Histogram("b.ms", bounds=(1.0, 5.0))
    ha.observe(2.0)
    hb.observe(2.0)
    with pytest.raises(MXNetError, match="conflicting"):
        telemetry.merge_snapshots([{"lat": ha.export()},
                                   {"lat": hb.export()}])


def test_merge_snapshots_kind_mismatch_raises():
    h = telemetry.Histogram("a.ms")
    h.observe(1.0)
    with pytest.raises(MXNetError):
        telemetry.merge_snapshots([{"x": 1}, {"x": h.export()}])
    with pytest.raises(MXNetError):
        telemetry.merge_snapshots([{"x": 1}, {"x": "one"}])
