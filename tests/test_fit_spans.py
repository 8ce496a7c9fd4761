"""The program's span API (``telemetry.span``) and its call sites inside
``Module.fit``: set-up's phases, the step's phases on the fused and the
classic path, JAX's own duration events as ``jax.*`` spans, the jit
entries gauge, and the named scopes in what the fused step traces."""
import re
import sys
import threading
import time
from collections import Counter

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry, tracing


@pytest.fixture(autouse=True)
def _isolated_telemetry():
    telemetry.reset()
    telemetry.enable()
    tracing.step_trace().reset()
    yield
    telemetry.reset()
    telemetry.disable()


def _net():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3), pad=(1, 1),
                             name="conv1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=5,
                                name="fc1")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _iter(steps, batch):
    x = np.random.RandomState(0).rand(steps * batch, 3, 8, 8) \
        .astype(np.float32)
    y = (np.arange(steps * batch) % 5).astype(np.float32)
    return mx.io.NDArrayIter(x, y, batch_size=batch)


def _fit(monkeypatch, fused, steps=6, batch=4, mod=None):
    from mxnet_tpu import xprof

    # telemetry's switch decides: an ``xprof.disable()`` that a test of
    # another file left behind on this worker does not
    monkeypatch.setattr(xprof, "_override", None)
    if fused:
        monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    else:
        monkeypatch.delenv("MXNET_TPU_FUSED_STEP", raising=False)
    mod = mod or mx.mod.Module(_net())
    mod.fit(_iter(steps, batch), num_epoch=1, optimizer="sgd",
            eval_metric="ce",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    return mod


# -- the API ---------------------------------------------------------------

def test_disabled_span_is_one_shared_object_and_allocates_nothing():
    telemetry.disable()
    first = telemetry.span("a")
    assert telemetry.span("b") is first
    with first as inside:
        assert inside is first
    first.cancel()
    assert first.elapsed_ms() == 0.0

    def many():
        for _ in range(2000):
            with telemetry.span("off"):
                pass

    many()                                   # whatever warms up, has
    before = sys.getallocatedblocks()
    many()
    assert sys.getallocatedblocks() - before <= 2
    telemetry.next_step()
    assert telemetry.spans() == [] and telemetry.snapshot() == {}


def test_nesting_records_parent_and_step():
    with telemetry.span("setup"):
        pass
    telemetry.next_step()
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            pass
        telemetry.next_step()               # a span keeps the step it
        with telemetry.span("late"):        # was opened in
            pass
    got = {s[0]: s for s in telemetry.spans()}
    assert [s[0] for s in telemetry.spans()] == ["setup", "inner", "late",
                                                 "outer"]
    assert got["setup"][4:] == (None, 0)
    assert got["outer"][4:] == (None, 1)
    assert got["inner"][4:] == ("outer", 1)
    assert got["late"][4:] == ("outer", 2)
    # start and duration on perf_counter, children inside the parent
    o, i = got["outer"], got["inner"]
    assert o[2] <= i[2] and i[2] + i[3] <= o[2] + o[3]
    assert abs(time.perf_counter() - (o[2] + o[3])) < 5.0
    telemetry.reset()
    with telemetry.span("after"):
        pass
    assert telemetry.spans()[0][5] == 0      # reset rewinds the step


def test_parent_is_the_enclosing_span_of_the_same_thread():
    def work():
        with telemetry.span("worker"):
            pass

    with telemetry.span("main"):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    got = {s[0]: s for s in telemetry.spans()}
    assert got["worker"][4] is None
    assert got["worker"][1] != got["main"][1]


def test_cancelled_span_leaves_no_entry_and_elapsed_runs():
    with telemetry.span("kept") as kept:
        with telemetry.span("dropped") as dropped:
            dropped.cancel()
        assert kept.elapsed_ms() >= 0.0
    assert [s[0] for s in telemetry.spans()] == ["kept"]
    assert "dropped_ms" not in telemetry.snapshot()["span"]
    with pytest.raises(ValueError):          # a span closes on an error
        with telemetry.span("raises"):
            raise ValueError("x")
    with telemetry.span("next"):
        pass
    assert telemetry.spans()[-1][4] is None  # ... and leaves the stack


def test_jax_duration_events_become_spans_once():
    telemetry.enable()                       # a second enable() must not
    with telemetry.span("caller"):           # register a second listener
        jax.jit(lambda x: x * 3 + 1)(np.ones(7, np.float32))
    got = Counter(s[0] for s in telemetry.spans())
    assert got["jax.trace"] == got["jax.lower"] == 1
    assert got["jax.backend_compile"] == 1
    for name, _tid, start, dur, parent, _step in telemetry.spans():
        if name.startswith("jax."):
            assert parent == "caller" and dur > 0
    caller = telemetry.spans()[-1]
    assert all(caller[2] <= s[2] + 1e-3 for s in telemetry.spans())
    telemetry.disable()
    n = len(telemetry.spans())
    jax.jit(lambda x: x * 5 + 2)(np.ones(7, np.float32))
    assert len(telemetry.spans()) == n


# -- the call sites --------------------------------------------------------

SETUP = {"fit.bind", "fit.init_params", "fit.init_optimizer",
         "fit.fused_build"}
JAX = {"jax.trace", "jax.lower", "jax.backend_compile", "jax.cache_read"}
# ``step.census`` / ``step.identity``: with telemetry on the fused step's
# program is read and its lowered text hashed once, inside ``step.build``
STEP = {True: {"fit.step", "fit.next", "step.marshal", "step.dispatch",
               "step.write_back", "fit.callbacks", "step.build",
               "step.census", "step.identity"},
        False: {"fit.step", "fit.next", "fit.forward_backward",
                "fit.update", "fit.update_metric", "fit.callbacks"}}


@pytest.mark.parametrize("fused", [True, False])
def test_fit_leaves_exactly_the_named_spans(monkeypatch, fused):
    steps = 6
    _fit(monkeypatch, fused, steps=steps)
    spans = telemetry.spans()
    count = Counter(s[0] for s in spans)
    assert set(count) - JAX == SETUP | STEP[fused]
    assert all(count[name] == 1 for name in SETUP)
    per_step = STEP[fused] - {"fit.next", "step.build", "step.census",
                              "step.identity"}
    assert all(count[name] == steps for name in per_step), count
    # the next() that found the epoch over is a span; its step is not
    assert count["fit.next"] == steps + 1
    if fused:
        assert count["step.build"] == 2      # _build, and the first call
        assert count["step.census"] == count["step.identity"] == 1
        assert {s[4] for s in spans if s[0] == "step.identity"} \
            == {"step.dispatch"}             # inside the first call's build
    by_step = {}
    for name, _tid, _t0, _dur, parent, step in spans:
        if name in SETUP:
            assert (parent, step) == (None, 0)
        elif name == "fit.step":
            assert parent is None
            by_step[step] = set()
    assert sorted(by_step) == list(range(1, steps + 1))
    for name, _tid, _t0, _dur, parent, step in spans:
        if parent == "fit.step" and step in by_step:
            by_step[step].add(name)
    want = per_step - {"fit.step"} | {"fit.next"}
    assert all(kids >= want - {"step.dispatch"} for kids in by_step.values())
    # all on the main thread
    assert {s[1] for s in spans} == {threading.get_ident()}


def test_record_step_takes_its_latency_from_the_fit_step_span(monkeypatch):
    _fit(monkeypatch, fused=True, steps=5)
    recs = tracing.step_trace().records()
    steps = [s for s in telemetry.spans() if s[0] == "fit.step"]
    assert len(recs) == len(steps) == 5
    for rec, (_n, _tid, _t0, dur, _p, _s) in zip(recs, steps):
        # recording is the step's last act: the span is longer by the
        # record alone
        assert 0 < rec["latency_ms"] <= dur * 1e3
        assert dur * 1e3 - rec["latency_ms"] < 50.0
    assert recs[0]["latency_ms"] == max(r["latency_ms"] for r in recs)


@pytest.mark.parametrize("xprof_on", [False, True])
def test_jit_entries_gauge_counts_what_the_jit_holds(monkeypatch, xprof_on):
    from mxnet_tpu import xprof

    monkeypatch.setattr(xprof, "enabled", lambda: xprof_on)
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    mod = mx.mod.Module(_net())
    mod.bind(data_shapes=[("data", (4, 3, 8, 8))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer(optimizer_params={"learning_rate": 0.1})
    metric = mx.metric.create("ce")
    fused = mod._fused_train_step(metric)

    def entries():
        return telemetry.peek("step.fused_jit_entries", kind="gauge")

    for batch in _iter(3, 4):                # a steady run: one program
        fused.step(batch, metric)
    assert entries() == fused.jit_entries() == 1
    for batch in _iter(2, 2):                # a second batch shape
        fused.step(batch, metric)
    assert entries() == fused.jit_entries() == 2
    assert telemetry.peek("step.fused_recompiles") == 2
    assert telemetry.peek("step.dispatches") == 5
    builds = [s for s in telemetry.spans() if s[0] == "step.build"]
    assert len(builds) == 3                  # _build once, two first calls


def _hlo_of_fused_step(monkeypatch, scopes):
    import contextlib

    if not scopes:
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
    lowered = []
    real_jit = jax.jit

    def spy(fn, **kw):
        jfn = real_jit(fn, **kw)
        if getattr(fn, "__name__", "") == "step":
            class Spy:
                # telemetry is on, so the step's jit is xprof's wrapper
                # around this one: it lowers, then runs what it built
                def lower(self, *args):
                    lowered.append(jfn.lower(*args))
                    return lowered[-1]

                def _cache_size(self):
                    return jfn._cache_size()
            return Spy()
        return jfn

    monkeypatch.setattr(jax, "jit", spy)
    _fit(monkeypatch, fused=True, steps=1)
    monkeypatch.undo()
    assert len(lowered) == 1
    return lowered[0]


def _strip(hlo_text):
    return re.sub(r",? ?metadata=\{[^}]*\}", "", hlo_text)


def test_named_scopes_change_metadata_only(monkeypatch):
    plain = _hlo_of_fused_step(monkeypatch, scopes=False)
    scoped = _hlo_of_fused_step(monkeypatch, scopes=True)
    a = plain.compile().as_text()
    b = scoped.compile().as_text()
    assert _strip(a) == _strip(b)
    assert a != b
    names = set(re.findall(r'op_name="([^"]*)"', b))
    for want in ("/fwd/", "/bwd/", "/update/", "/metric/",
                 "Convolution:conv1", "BatchNorm:bn1",
                 "FullyConnected:fc1"):
        assert any(want in n for n in names), want
    assert not any("Convolution:conv1" in n
                   for n in re.findall(r'op_name="([^"]*)"', a))
    # the backward ops carry the node they differentiate
    assert any("/bwd/" in n and "Convolution:conv1" in n for n in names)


def test_profiler_trace_holds_one_step_and_one_dispatch_event_a_step(
        monkeypatch, tmp_path):
    steps = 5
    mod = _fit(monkeypatch, fused=True, steps=2)     # compiled, warm
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _fit(monkeypatch, fused=True, steps=steps, mod=mod)
    finally:
        jax.profiler.stop_trace()
    files = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert len(files) == 1
    data = jax.profiler.ProfileData.from_file(str(files[0]))
    events = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events if e.name.startswith("mx:")]
    count = Counter(name for name, _s, _e in events)
    assert count["mx:step.dispatch"] == steps
    assert count["mx:step.marshal"] == steps
    # one per step, and the one whose next() found the epoch over
    assert count["mx:fit.step"] == steps + 1
    step_spans = [(s, e) for name, s, e in events if name == "mx:fit.step"]
    for name, s, e in events:
        if name == "mx:step.dispatch":
            assert sum(lo <= s and e <= hi for lo, hi in step_spans) == 1
