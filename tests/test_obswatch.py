"""Fleet-wide metric federation: prometheus round trip back into
payload shape, the HTTP and in-process scrape targets, bucket-merged
rollups headlining the router-view latency, the durable JSONL ring
store (rollover, retention, torn trailing lines, dotted-path queries),
multi-window SLO burn-rate alerting under a fake clock, the ObsWatch
loop end to end against a fake fleet, and the fleet-health report
view."""
import json
import os
import sys

import pytest

import mxnet_tpu as mx  # noqa: F401 (package init wires telemetry hooks)
from mxnet_tpu import fleet, obswatch, telemetry, tracing
from mxnet_tpu.base import MXNetError

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import trace_report  # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_telemetry():
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.reset()
    telemetry.disable()


def _hist(values, include_sample=True):
    h = telemetry.Histogram("t.ms")
    for v in values:
        h.observe(v)
    return h.export(include_sample=include_sample)


# -- federation ----------------------------------------------------------

def _payload(rid, served, breaches, in_flight, lats, up=True):
    return {"rid": rid, "up": up,
            "health": {"status": "ok" if up else "down"},
            "metrics": {"serve.requests_served": served,
                        "serve.slo_breaches": breaches,
                        "serve.in_flight": float(in_flight),
                        "serve.request_ms": _hist(lats)}}


def test_federate_counters_sum_gauges_fan_out():
    """Counters merge by sum into the fleet row; gauges stay labeled
    per replica so a hot replica is visible, not averaged away."""
    p0 = _payload("r0", 10, 1, 2, [1.0] * 20)
    p1 = _payload("r1", 30, 0, 5, [2.0] * 20)
    stats = {"replicas": {
        "r0": {"state": "up", "breaker": {"state": "closed"}},
        "r1": {"state": "up", "breaker": {"state": "open"}}}}
    r = obswatch.federate([p0, p1], router_stats=stats, ts=100.0)
    assert r["ts"] == 100.0 and r["kind"] == "rollup"
    f = r["fleet"]
    assert f["replicas"] == 2 and f["up"] == 2
    assert f["served"] == 40 and f["slo_breaches"] == 1
    assert f["in_flight"] == 7.0
    assert f["breakers_open"] == 1
    rows = r["replica_rows"]
    assert rows["r0"]["served"] == 10 and rows["r1"]["served"] == 30
    assert rows["r0"]["in_flight"] == 2.0 and rows["r1"]["in_flight"] == 5.0
    assert rows["r1"]["breaker"] == "open"
    # per-replica percentiles come from each replica's own histogram
    assert rows["r0"]["p50_ms"] == pytest.approx(1.0)
    assert rows["r1"]["p50_ms"] == pytest.approx(2.0)
    # fleet latency merges bucket-wise across replicas (no router view
    # here, so the scheduler-side merge is the headline)
    assert 1.0 <= f["p50_ms"] <= 2.0
    assert "sample" not in f["request_ms"]  # store stays slim


def test_federate_headlines_router_view():
    """With a router histogram in the merge, fleet percentiles come
    from the client-experienced series, not the scheduler view."""
    p = _payload("r0", 100, 0, 0, [1.0] * 50)
    rm = {"router.request_ms": _hist([10.0] * 50)}
    r = obswatch.federate([p], router_metrics=rm, ts=1.0)
    assert r["fleet"]["p50_ms"] == pytest.approx(10.0)
    # the per-replica row still shows the scheduler view
    assert r["replica_rows"]["r0"]["p50_ms"] == pytest.approx(1.0)


def test_federate_down_replica_rows():
    p0 = _payload("r0", 10, 0, 0, [1.0])
    p1 = {"rid": "r1", "up": False,
          "health": {"status": "down", "error": "boom"}, "metrics": {}}
    r = obswatch.federate([p0, p1], ts=1.0)
    assert r["fleet"]["up"] == 1 and r["fleet"]["replicas"] == 2
    assert r["replica_rows"]["r1"]["status"] == "down"


def test_goodput_from_served_delta():
    r0 = {"ts": 10.0, "fleet": {"served": 100}}
    r1 = {"ts": 12.0, "fleet": {"served": 200}}
    assert obswatch.goodput(r0, r1) == pytest.approx(50.0)
    assert obswatch.goodput(r0, r0) is None  # zero dt is not a rate


# -- prometheus round trip -----------------------------------------------

def test_prometheus_round_trip():
    """tracing.prometheus_text -> obswatch.parse_prometheus_text
    reconstructs the flat payload: counters as ints, gauges as floats,
    histograms reassembled from _bucket/_sum/_count."""
    telemetry.inc("engine.push", 7)
    telemetry.set_gauge("io.ring_occupancy", 3.0)
    for v in (1.0, 2.0, 3.0, 4.0):
        telemetry.observe("profiler.step_ms", v)
    parsed = obswatch.parse_prometheus_text(tracing.prometheus_text())
    assert parsed["engine.push"] == 7
    assert parsed["io.ring_occupancy"] == 3.0
    h = parsed["profiler.step_ms"]
    assert h["count"] == 4 and h["sum"] == pytest.approx(10.0)
    assert h["mean"] == pytest.approx(2.5)
    # cumulative finite-bound counts survive the trip
    b = dict(zip(h["buckets"]["bounds"], h["buckets"]["counts"]))
    assert b[1.0] == 1 and b[2.5] == 2 and b[5.0] == 4
    # and the reassembled export merges with a native one
    native = _hist([1.0, 2.0, 3.0, 4.0], include_sample=False)
    merged = telemetry.merge_snapshots(
        [{"profiler.step_ms": h}, {"profiler.step_ms": native}])
    assert merged["profiler.step_ms"]["count"] == 8


def test_http_target_scrapes_metrics_server():
    telemetry.inc("engine.push", 5)
    server = tracing.MetricsServer(0)
    try:
        out = obswatch.HttpTarget("r9", "127.0.0.1", server.port).scrape()
    finally:
        server.close()
    assert out["rid"] == "r9" and out["up"]
    assert out["metrics"]["engine.push"] == 5
    assert out["health"].get("status")


def test_http_target_down_on_refused_connection():
    out = obswatch.HttpTarget("r9", "127.0.0.1", 1, timeout_s=0.2).scrape()
    assert not out["up"] and out["health"]["status"] == "down"


# -- durable time-series store -------------------------------------------

def test_store_rollover_and_retention(tmp_path):
    store = obswatch.TimeSeriesStore(str(tmp_path), seg_records=5,
                                     seg_keep=2)
    for i in range(23):
        store.append({"ts": float(i), "fleet": {"served": 2 * i}})
    # 23 records over 5-record segments -> segments 0..4; rollover
    # prunes the closed ring down to seg_keep before opening the next
    # segment, so at most seg_keep+1 segments ever exist on disk
    assert store.segments() == [2, 3, 4]
    recs = store.records()
    assert len(recs) == 13 and recs[0]["ts"] == 10.0
    with open(os.path.join(str(tmp_path), store.MANIFEST)) as f:
        manifest = json.load(f)
    assert manifest["current"] == 4 and manifest["seg_keep"] == 2


def test_store_query_dotted_path_and_window(tmp_path):
    store = obswatch.TimeSeriesStore(str(tmp_path), seg_records=100,
                                     seg_keep=2)
    for i in range(10):
        store.append({"ts": float(i), "fleet": {"served": i,
                                                "p99_ms": 1.5 * i}})
    pts = store.query("fleet.p99_ms", t_min=3.0, t_max=6.0)
    assert [t for t, _ in pts] == [3.0, 4.0, 5.0, 6.0]
    assert pts[-1][1] == pytest.approx(9.0)
    assert store.query("fleet.nope") == []


def test_store_skips_torn_trailing_line(tmp_path):
    store = obswatch.TimeSeriesStore(str(tmp_path), seg_records=100,
                                     seg_keep=2)
    for i in range(3):
        store.append({"ts": float(i), "v": i})
    seg = os.path.join(str(tmp_path), "segment-0.jsonl")
    with open(seg, "a") as f:
        f.write('{"ts": 99, "v"')  # crash mid-append: no newline, torn
    assert len(store.records()) == 3
    # a fresh store over the same dir keeps appending past the tear
    store2 = obswatch.TimeSeriesStore(str(tmp_path), seg_records=100,
                                      seg_keep=2)
    store2.append({"ts": 100.0, "v": 100})
    assert store2.query("v")[-1] == (100.0, 100)


# -- burn-rate monitor (fake clock) --------------------------------------

def _roll(ts, served, bad):
    return {"ts": ts, "fleet": {"served": served, "slo_breaches": bad}}


def test_burn_alert_fires_before_budget_spent():
    mon = obswatch.BurnRateMonitor(slo_target=0.9, fast_s=10.0,
                                   slow_s=60.0, threshold=2.0,
                                   min_events=5)
    mon.update(_roll(0.0, 0, 0))
    v = mon.update(_roll(5.0, 100, 50))  # 50% bad / 10% budget = 5x burn
    assert v["alert"]
    assert v["fast_burn"] == pytest.approx(5.0)
    assert v["slow_burn"] == pytest.approx(5.0)
    # the page fires while budget remains: 5x burn for 5s of a 60s
    # window spends ~42% of the budget
    assert 0 < v["budget_spent"] < 1.0


def test_burn_blip_does_not_page():
    """A short spike lights the fast window only; the slow window
    filters it, so no alert."""
    mon = obswatch.BurnRateMonitor(slo_target=0.9, fast_s=10.0,
                                   slow_s=100.0, threshold=2.0,
                                   min_events=5)
    for t in range(0, 91, 5):
        mon.update(_roll(float(t), 20 * t, 0))  # long clean history
    v = mon.update(_roll(95.0, 1900, 50))       # 5s spike
    assert v["fast_burn"] > 2.0 and v["slow_burn"] < 2.0
    assert not v["alert"]


def test_burn_min_events_guard():
    mon = obswatch.BurnRateMonitor(slo_target=0.9, fast_s=10.0,
                                   slow_s=60.0, threshold=2.0,
                                   min_events=50)
    mon.update(_roll(0.0, 0, 0))
    v = mon.update(_roll(5.0, 10, 10))  # hot, but only 10 events
    assert not v["alert"]


def test_burn_clears_when_traffic_recovers():
    mon = obswatch.BurnRateMonitor(slo_target=0.9, fast_s=5.0,
                                   slow_s=20.0, threshold=2.0,
                                   min_events=5)
    mon.update(_roll(0.0, 0, 0))
    assert mon.update(_roll(2.0, 100, 60))["alert"]
    # breaches stop; the fast window drains first
    assert not mon.update(_roll(10.0, 1000, 60))["alert"]


def test_burn_requires_error_budget():
    with pytest.raises(MXNetError):
        obswatch.BurnRateMonitor(slo_target=1.0)


# -- ObsWatch end to end over a fake fleet -------------------------------

class _FakeReplica:
    def __init__(self):
        self.served = 0
        self.bad = 0
        self.alive = True

    def health(self):
        if not self.alive:
            raise RuntimeError("dead")
        return {"status": "ok"}

    def metrics(self):
        return {"serve.requests_served": self.served,
                "serve.slo_breaches": self.bad,
                "serve.in_flight": 0.0,
                "serve.request_ms": _hist([1.0] * max(1, self.served))}


class _FakeRouter:
    def __init__(self, n=2):
        self._reps = [_FakeReplica() for _ in range(n)]

    def replicas(self):
        return [("r%d" % i, r) for i, r in enumerate(self._reps)]

    def stats(self):
        return {"replicas": {}}

    def metrics_payload(self):
        return {"router.served": sum(r.served for r in self._reps)}


def test_obswatch_tick_persists_and_alerts(tmp_path):
    clk = [0.0]
    router = _FakeRouter()
    store = obswatch.TimeSeriesStore(str(tmp_path), seg_records=100,
                                     seg_keep=2)
    mon = obswatch.BurnRateMonitor(slo_target=0.9, fast_s=10.0,
                                   slow_s=60.0, threshold=2.0,
                                   min_events=5)
    watch = obswatch.ObsWatch(router, store=store, monitor=mon,
                              interval_ms=3600e3, clock=lambda: clk[0])
    try:
        watch.tick()
        for rep in router._reps:
            rep.served, rep.bad = 50, 25
        clk[0] = 5.0
        r = watch.tick()
        assert r["burn"]["alert"] and watch.alerts == 1
        # the rising edge landed a slo_burn_alert step record, which is
        # what FleetHealthDetector keys on
        recs = tracing.step_trace().records()
        assert any(rec.get("slo_burn_alert") for rec in recs)
        ev = tracing.FleetHealthDetector().check(
            [rec for rec in recs if rec.get("slo_burn_alert")][-1])
        assert ev and ev.get("slo_burn_alert")
        # the registered health probe reports the burn while alerting
        probe = watch._probe()
        assert probe and probe["budget_spent"] == \
            r["burn"]["budget_spent"]
        # and every tick landed durably
        assert len(store.records()) == 2
        assert store.query("burn.fast_burn")[-1][1] > 2.0
        # a second hot tick is NOT a second alert (edge, not level)
        clk[0] = 6.0
        watch.tick()
        assert watch.alerts == 1
    finally:
        watch.close()


def test_obswatch_survives_dead_replica(tmp_path):
    router = _FakeRouter()
    router._reps[1].alive = False
    store = obswatch.TimeSeriesStore(str(tmp_path), seg_records=100,
                                     seg_keep=2)
    mon = obswatch.BurnRateMonitor(slo_target=0.9, fast_s=10.0,
                                   slow_s=60.0, threshold=2.0)
    with obswatch.ObsWatch(router, store=store, monitor=mon,
                           interval_ms=3600e3, clock=lambda: 1.0) as w:
        r = w.tick()
    assert r["fleet"]["up"] == 1
    assert r["replica_rows"]["r1"]["status"] == "down"


def test_obswatch_over_real_inproc_fleet(tmp_path):
    """The scraper against a real router + InProc replicas: served
    counters federate and the router-view latency headline exists."""
    # no attempt is given up while its replica compiles its first batch
    # (500 ms by default: a loaded worker retried four of the eight, and
    # both attempts were served)
    router = fleet.FleetRouter(fleet.in_process(fleet.demo_server_factory),
                               2, health_interval_s=0.02,
                               attempt_timeout_ms=30e3, deadline_ms=60e3)
    try:
        import numpy as np
        x = np.zeros((1, 8), dtype=np.float32)
        futs = [router.submit([x]) for _ in range(8)]
        for f in futs:
            f.result(timeout=30)
        store = obswatch.TimeSeriesStore(str(tmp_path), seg_records=100,
                                         seg_keep=2)
        mon = obswatch.BurnRateMonitor(slo_target=0.5, fast_s=10.0,
                                       slow_s=60.0, threshold=1e9)
        with obswatch.ObsWatch(router, store=store, monitor=mon,
                               interval_ms=3600e3) as w:
            r = w.tick()
    finally:
        router.close()
    assert r["fleet"]["served"] == 8 and r["fleet"]["up"] == 2
    assert r["fleet"]["p50_ms"] > 0  # router-view histogram populated
    assert sum(row["served"] for row in r["replica_rows"].values()) == 8


# -- fleet-health view ---------------------------------------------------

def test_fleet_health_view_renders():
    rec = {
        "federation": {"fed_goodput_rps": 100.0,
                       "client_goodput_rps": 101.0,
                       "goodput_rel_err": 0.01, "fed_p99_ms": 5.0,
                       "client_p99_ms": 5.1, "p99_rel_err": 0.02},
        "final_rollup": {
            "ts": 10.0, "fleet": {"replicas": 2, "up": 2, "served": 500,
                                  "slo_breaches": 3, "in_flight": 1,
                                  "breakers_open": 0, "p50_ms": 2.0,
                                  "p99_ms": 5.0},
            "replica_rows": {"r0": {"status": "ok", "state": "up",
                                    "breaker": "closed", "served": 250,
                                    "slo_breaches": 1, "in_flight": 1,
                                    "p50_ms": 2.0, "p99_ms": 5.0}}},
        "burn": {"alert_fired": True, "alert_at_s": 0.4,
                 "budget_spent_at_alert": 0.2, "fast_burn": 1.6,
                 "slow_burn": 1.6},
        "series": {"burn.budget_spent": [[0.0, 0.0], [1.0, 0.5]]},
    }
    out = trace_report.render_fleet_health(rec)
    assert "r0" in out and "FLEET" in out
    assert "federation agreement" in out
    assert "SLO burn: ALERT" in out and "20% of error budget" in out
    assert "budget burn-down" in out


def test_fleet_health_view_incomplete_safe():
    out = trace_report.render_fleet_health(
        {"incomplete": "fleet obswatch phase did not run"})
    assert "INCOMPLETE" in out
