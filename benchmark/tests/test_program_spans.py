"""The readers of the program's own spans: the arithmetic on hand-made
rings, a program that has no spans, a ring that has dropped some, and a
rehearsal of a traced run at toy width in which they are held against
what the harness times from outside."""
import json
import time

import pytest

import toy
from benchmark import harness
from benchmark.drivers import fit
from benchmark.trace import program_spans as ps
from test_rehearsal import on_cpu   # noqa: F401 (a fixture)

NEW = ["fit_step_p95_ms", "fit_host_ms_per_step", "input_next_ms_per_step",
       "step_jit_entries", "setup_bind_s", "setup_trace_lower_s",
       "setup_cache_read_s"]


def _reader(name):
    """The reader as the harness loads and calls it."""
    spec = {"per_layer": [{"name": name, "unit": "x"}]}
    return lambda *args: harness.read_per_layer(
        spec, "cell", *args).get(name, {}).get("value")


def _entry(name, start, end, parent=None, step=0, tid=1):
    return (name, tid, start, end - start, parent, step)


def test_covered_counts_each_instant_once_and_takes_children_out():
    ring = [_entry("jax.trace", 1.0, 2.0, "fit.bind"),
            _entry("jax.trace", 1.2, 1.4, "fit.bind"),     # nested
            _entry("jax.lower", 2.0, 2.5, "fit.bind"),
            _entry("jax.cache_read", 2.6, 2.9, "fit.bind"),
            _entry("jax.backend_compile", 2.5, 3.0, "fit.bind"),
            _entry("fit.bind", 0.0, 4.0),
            _entry("fit.init_params", 4.0, 5.0),
            _entry("jax.trace", 10.0, 11.0, "step.dispatch", 1),
            _entry("jax.trace", 100.0, 101.0, None, 0, tid=2)]
    assert ps.covered(ring[:6], ps.JAX_TRACE_LOWER) == pytest.approx(1.5)
    assert ps.covered(ring[:6], ps.JAX_BUILD,
                      less=ps.JAX_TRACE_LOWER) == pytest.approx(0.5)
    assert ps.covered(ring, ps.SETUP, less=ps.JAX_TRACE_LOWER
                      + ps.JAX_BUILD) == pytest.approx(5.0 - 2.0)
    # clipped to a stretch, and thread by thread
    assert ps.covered(ring, ("jax.trace",), 10.5, 100.5) \
        == pytest.approx(1.0)
    steps = [_entry("fit.step", 0.9, 2.0), _entry("fit.step", 2.0, 3.0),
             _entry("fit.step", 3.0, 4.5)]
    assert ps.whole_steps_ms(steps, 1.0, 4.0) == [pytest.approx(1000.0)]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_gives_no_metric(name, capsys):
    """The parent of the PR that placed the spans: an empty ring and no
    gauge. The reader finds nothing to read and raises nothing."""
    from mxnet_tpu import telemetry

    telemetry.reset()
    value = _reader(name)({}, {"steps": 4}, [("iter.next", 1.0, 2.0)], {})
    assert value is None
    if name != "step_jit_entries":
        assert "recorded no spans" in capsys.readouterr().out


def test_a_ring_that_dropped_entries_gives_no_metric(monkeypatch, capsys):
    from mxnet_tpu import env, telemetry

    cap = env.get("MXNET_TPU_TELEMETRY_SPAN_CAP")
    full = [_entry("fit.step", 10.0 + i, 11.0 + i) for i in range(cap)]
    monkeypatch.setattr(telemetry, "spans", lambda: list(full))
    spans = [("iter.next", 5.0, 6.0), ("iter.next", 20.0, 21.0)]
    # the stretch began before the oldest entry the ring still holds
    assert _reader("fit_step_p95_ms")({}, {"steps": 4}, spans, {}) is None
    assert _reader("setup_bind_s")({}, {"steps": 4}, spans, {}) is None
    assert capsys.readouterr().out.count("has dropped entries") == 2
    # a stretch the ring still covers whole is read
    spans = [("iter.next", 12.0, 12.5), ("iter.next", 20.0, 21.0)]
    assert _reader("fit_step_p95_ms")({}, {"steps": 4}, spans, {}) \
        == pytest.approx(1000.0)


def test_traced_rehearsal_agrees_with_what_the_harness_times(
        on_cpu, capsys, monkeypatch):   # noqa: F811
    """``--trace 1`` at toy width on the CPU: all seven are on the line,
    and the program's spans agree with the harness's on the same clock."""
    from benchmark.trace import reduce as R
    from mxnet_tpu import telemetry

    real_reduce, real_read = R.reduce, harness.read_per_layer
    seen = {}

    def with_a_device_plane(trace, steps):
        start = R.host_spans(trace)[0][1]
        trace["planes"].append({"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops",
             "events": [["fusion.1", "fusion:kOutput", start, 1e6]]}]})
        seen["mx"] = [e for p in trace["planes"] for ln in p["lines"]
                      for e in ln["events"] if e[0].startswith("mx:")]
        seen["bench"] = R.host_spans(trace)
        return real_reduce(trace, steps)

    def keep(spec, name, trace, counters, spans, info):
        seen.update(counters=counters, spans=spans, info=info,
                    ring=telemetry.spans())
        return real_read(spec, name, trace, counters, spans, info)

    monkeypatch.setattr(R, "reduce", with_a_device_plane)
    monkeypatch.setattr(harness, "read_per_layer", keep)
    cell = toy.cell("resnet", fused=True, compute_dtype="float32")
    fit.run(cell, seed=11, seconds=3.0, trace=True,
            t_start=time.perf_counter())
    out = capsys.readouterr().out.strip().splitlines()
    metrics = json.loads(out[-1])["metrics"]
    assert set(NEW) <= set(metrics)
    value = {k: v["value"] for k, v in metrics.items()}
    steps, spans = seen["counters"]["steps"], seen["spans"]
    lo, hi = ps.stretch(spans)

    assert value["step_jit_entries"] == 1.0
    assert value["fit_dispatches_per_step"] == 1.0
    # cross-check 1: the fit.step spans fill the traced stretch
    in_steps = ps.covered(seen["ring"], ("fit.step",), lo, hi)
    assert in_steps == pytest.approx(seen["info"]["traced_seconds"],
                                     rel=0.02)
    # cross-check 2: fit.next from inside is iter.next from outside
    outside = sum(t1 - t0 for name, t0, t1 in spans
                  if name == "iter.next") * 1e3 / steps
    # (within 5% on the chip, where a step takes 50-100 ms; at 2 ms a
    # step the two span pairs between the clocks, ~10 us, are a tenth)
    assert value["input_next_ms_per_step"] == pytest.approx(outside,
                                                            abs=0.05)
    assert 0 < value["fit_host_ms_per_step"] \
        < seen["info"]["traced_seconds"] * 1e3 / steps
    assert value["fit_step_p95_ms"] > 0
    # set-up's parts do not overlap, and lie inside set-up
    setup_s = float(next(l for l in out if l.startswith("window:"))
                    .split("set-up ")[1].split(" s")[0])
    parts = [value[k] for k in ("setup_bind_s", "setup_trace_lower_s",
                                "setup_cache_read_s")]
    assert all(p > 0 for p in parts) and sum(parts) < setup_s
    # in the trace itself: one mx:fit.step a step, each mx:step.dispatch
    # inside a bench:fit_loop span; the harness's readers saw only bench:
    loops = [(s, e) for name, s, e in seen["bench"] if name == "fit_loop"]
    dispatches = [e for e in seen["mx"] if e[0] == "mx:step.dispatch"]
    assert len(dispatches) == steps
    for _name, _tag, start, dur in dispatches:
        assert any(s <= start and start + dur <= e for s, e in loops)
    assert abs(sum(e[0] == "mx:fit.step" for e in seen["mx"]) - steps) <= 1
    assert all(not name.startswith("mx:") for name, _s, _e in seen["bench"])
