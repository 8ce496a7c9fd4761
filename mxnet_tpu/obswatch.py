"""Fleet-wide observability federation: scrape, merge, persist, alert.

Every observability plane before this one is per-process — each replica
serves its own ``/metrics`` (:mod:`mxnet_tpu.tracing`) and nobody can
answer "what is the fleet's p99 right now". This module is the single
pane of glass:

* **Scraper** — polls every replica the
  :class:`~mxnet_tpu.fleet.FleetRouter` drives. InProc replicas expose
  the same payload through a direct callable (``Replica.metrics()`` /
  ``health()``), so federation works without sockets; HTTP targets
  (a subprocess replica running a :class:`~mxnet_tpu.tracing.MetricsServer`)
  are scraped over ``/metrics`` + ``/healthz`` and parsed from the
  Prometheus text exposition.
* **Federation** — counters merge by sum, gauges by labeled per-replica
  fan-out (the rollup keeps each replica's row), histograms bucket-wise
  via :func:`mxnet_tpu.telemetry.merge_snapshots` — fleet p50/p99/p999
  latency, total goodput, per-replica in-flight, breaker states.
* **Durable time-series** — :class:`TimeSeriesStore`, append-only JSONL
  ring segments (one atomic ``O_APPEND`` write per record, the PR 11
  crash-safety idiom; the manifest goes through
  :func:`mxnet_tpu.checkpoint.atomic_writer`), bounded retention,
  queryable by metric path + time window.
* **SLO burn-rate** — :class:`BurnRateMonitor` computes multi-window
  (fast/slow) burn rates from the stored rollups; when both windows
  burn past the threshold it fires a
  :class:`~mxnet_tpu.tracing.FleetHealthDetector` event
  (``slo_burn_alert`` in the step record) and flips a registered
  ``/healthz`` probe to degraded — the page fires while error budget
  remains, not after it is spent.

All knobs are ``MXNET_TPU_OBSWATCH_*`` (docs/env_vars.md); every
constructor takes an injectable ``clock`` so the burn-rate math is
testable under a fake clock.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
import urllib.error
import urllib.request
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from . import checkpoint as _ckpt
from . import env as _env
from . import telemetry as _tel
from . import tracing as _tracing
from .base import MXNetError

__all__ = ["ScrapeTarget", "InProcTarget", "HttpTarget", "FleetScraper",
           "federate", "parse_prometheus_text", "TimeSeriesStore",
           "BurnRateMonitor", "ObsWatch", "goodput"]

_log = logging.getLogger("mxnet_tpu.obswatch")


# ---------------------------------------------------------------------------
# scrape targets
# ---------------------------------------------------------------------------

class ScrapeTarget:
    """One replica's metrics+health source. ``scrape()`` returns a
    normalized payload::

        {"rid": str, "up": bool, "health": dict, "metrics": {name: export}}

    ``metrics`` is flat ``dotted.name -> export`` (int counter, float
    gauge, dict histogram) — the same shape
    :meth:`mxnet_tpu.serving.BatchScheduler.metrics_payload` emits, so
    InProc and HTTP targets federate identically."""

    rid: str = "?"

    def scrape(self) -> dict:
        raise NotImplementedError


class InProcTarget(ScrapeTarget):
    """Direct-callable target: no socket, no serialization — the
    in-process replica hands over its payload dicts."""

    def __init__(self, rid: str, replica):
        self.rid = rid
        self._replica = replica

    def scrape(self) -> dict:
        out = {"rid": self.rid, "up": False, "health": {}, "metrics": {}}
        try:
            out["health"] = self._replica.health() or {}
            out["up"] = True
        except Exception as e:     # noqa: BLE001 (a dead replica scrapes as down)
            out["health"] = {"status": "down", "error": str(e)}
        try:
            m = self._replica.metrics()
            if m:
                out["metrics"] = m
        except Exception as e:     # noqa: BLE001
            _log.debug("metrics scrape failed for %s: %s", self.rid, e)
        return out


class HttpTarget(ScrapeTarget):
    """Socket target: a replica running the tracing tier's
    :class:`~mxnet_tpu.tracing.MetricsServer`."""

    def __init__(self, rid: str, host: str, port: int,
                 timeout_s: float = 5.0):
        self.rid = rid
        self._base = "http://%s:%d" % (host, int(port))
        self._timeout = float(timeout_s)

    def _get(self, path: str) -> Tuple[int, str]:
        with urllib.request.urlopen(self._base + path,
                                    timeout=self._timeout) as resp:
            return resp.status, resp.read().decode()

    def scrape(self) -> dict:
        out = {"rid": self.rid, "up": False, "health": {}, "metrics": {}}
        try:
            _, body = self._get("/metrics")
            out["metrics"] = parse_prometheus_text(body)
        except Exception as e:     # noqa: BLE001
            out["health"] = {"status": "down", "error": str(e)}
            return out
        try:
            status, body = self._get("/healthz")
            out["health"] = json.loads(body)
            out["up"] = status == 200
        except urllib.error.HTTPError as e:   # 503 = degraded, still up
            try:
                out["health"] = json.loads(e.read().decode())
            except Exception:      # noqa: BLE001
                out["health"] = {"status": "degraded"}
            out["up"] = True
        except Exception as e:     # noqa: BLE001
            out["health"] = {"status": "down", "error": str(e)}
        return out


def parse_prometheus_text(text: str) -> Dict[str, object]:
    """Parse the tracing tier's exposition back into the flat
    ``name -> export`` payload shape. Histograms reassemble from their
    ``_bucket``/``_sum``/``_count`` series (cumulative finite-bound
    counts; the ``+Inf`` sample becomes ``count``). The ``mxnet_tpu_``
    prefix is stripped and the first underscore restored to a dot
    (``mxnet_tpu_serve_request_ms`` -> ``serve.request_ms``) so HTTP
    payloads merge with InProc ones."""
    types: Dict[str, str] = {}
    raw: Dict[str, list] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split()
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[parts[2]] = parts[3]
            continue
        name_labels, _, value = line.rpartition(" ")
        if "{" in name_labels:
            name, labels = name_labels.split("{", 1)
            labels = labels.rstrip("}")
        else:
            name, labels = name_labels, ""
        raw.setdefault(name, []).append((labels, value))

    def _label(labels: str, key: str) -> Optional[str]:
        marker = key + '="'
        if marker not in labels:
            return None
        return labels.split(marker, 1)[1].split('"', 1)[0]

    out: Dict[str, object] = {}
    hist_parts: Dict[str, dict] = {}
    for name, samples in raw.items():
        base = None
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and types.get(name[:-len(suffix)]) \
                    == "histogram":
                base = name[:-len(suffix)]
                h = hist_parts.setdefault(base, {"bounds": [], "cum": {},
                                                 "sum": 0.0, "count": 0})
                for labels, value in samples:
                    if suffix == "_bucket":
                        le = _label(labels, "le")
                        if le == "+Inf":
                            h["count"] = max(h["count"], int(float(value)))
                        elif le is not None:
                            h["cum"][float(le)] = int(float(value))
                    elif suffix == "_sum":
                        h["sum"] = float(value)
                    else:
                        h["count"] = int(float(value))
                break
        if base is not None:
            continue
        mtype = types.get(name, "gauge")
        labels, value = samples[-1]
        key = _denormalize_name(name)
        out[key] = int(float(value)) if mtype == "counter" else float(value)
    for base, h in hist_parts.items():
        bounds = sorted(h["cum"])
        counts = [h["cum"][b] for b in bounds]
        n = h["count"]
        ex: dict = {"count": n,
                    "buckets": {"bounds": bounds, "counts": counts}}
        if n:
            ex["sum"] = h["sum"]
            ex["mean"] = h["sum"] / n
            for q, key in ((0.5, "p50"), (0.9, "p90"), (0.99, "p99")):
                v = _tel.bucket_quantile(ex["buckets"], n, q)
                if v is not None:
                    ex[key] = v
        out[_denormalize_name(base)] = ex
    return out


def _denormalize_name(prom_name: str) -> str:
    name = prom_name
    if name.startswith("mxnet_tpu_"):
        name = name[len("mxnet_tpu_"):]
    return name.replace("_", ".", 1)


# ---------------------------------------------------------------------------
# federation
# ---------------------------------------------------------------------------

def _hist_quantile_ms(ex: Optional[dict], q: float) -> Optional[float]:
    if not ex or not ex.get("count"):
        return None
    sample = ex.get("sample")
    if sample:
        return _tel.sample_quantile(sample, q)
    return _tel.bucket_quantile(ex.get("buckets") or {}, ex["count"], q,
                                hi=ex.get("max"))


def federate(payloads: Sequence[dict],
             router_stats: Optional[dict] = None,
             router_metrics: Optional[dict] = None,
             ts: Optional[float] = None) -> dict:
    """Merge N scrape payloads into one fleet rollup: a per-replica row
    each (gauge fan-out: in-flight, served, status, breaker state) plus
    one fleet row (counter sums, bucket-merged latency histogram with
    fleet p50/p99/p999). ``router_stats`` (from
    :meth:`~mxnet_tpu.fleet.FleetRouter.stats`) contributes the
    router-side view — breaker/state per replica — that replicas cannot
    see about themselves."""
    router_replicas = (router_stats or {}).get("replicas", {})
    rows: Dict[str, dict] = {}
    merged = _tel.merge_snapshots(
        [p.get("metrics") or {} for p in payloads]
        + ([router_metrics] if router_metrics else []))
    up = 0
    for p in payloads:
        rid = p.get("rid", "?")
        health = p.get("health") or {}
        m = p.get("metrics") or {}
        lat = m.get("serve.request_ms")
        row = {
            "up": bool(p.get("up")),
            "status": health.get("status", "down"),
            "in_flight": m.get("serve.in_flight",
                               health.get("in_flight", 0)),
            "served": m.get("serve.requests_served",
                            health.get("requests_served", 0)),
            "slo_breaches": m.get("serve.slo_breaches", 0),
        }
        for q, key in ((0.5, "p50_ms"), (0.99, "p99_ms"),
                       (0.999, "p999_ms")):
            v = _hist_quantile_ms(lat, q)
            if v is not None:
                row[key] = round(v, 3)
        rview = router_replicas.get(rid)
        if rview:
            row["state"] = rview.get("state")
            row["breaker"] = (rview.get("breaker") or {}).get("state")
        if row["up"]:
            up += 1
        rows[rid] = row
    # fleet percentiles headline the router-view (client-experienced)
    # latency when the router contributed its histogram; the merged
    # scheduler-side series is the fallback for routerless federations
    fleet_lat = merged.get("router.request_ms") \
        or merged.get("serve.request_ms")
    fleet = {
        "replicas": len(payloads),
        "up": up,
        "served": merged.get("serve.requests_served", 0),
        "slo_breaches": merged.get("serve.slo_breaches", 0),
        "in_flight": merged.get("serve.in_flight", 0.0),
        "breakers_open": sum(
            1 for r in rows.values() if r.get("breaker") == "open"),
    }
    for q, key in ((0.5, "p50_ms"), (0.99, "p99_ms"), (0.999, "p999_ms")):
        v = _hist_quantile_ms(fleet_lat, q)
        if v is not None:
            fleet[key] = round(v, 3)
    rollup = {"ts": round(time.time() if ts is None else ts, 6),
              "kind": "rollup", "replica_rows": rows, "fleet": fleet}
    if fleet_lat:
        # the merged histogram rides along (without the raw sample) so
        # the store stays queryable for latency distributions
        slim = {k: v for k, v in fleet_lat.items() if k != "sample"}
        rollup["fleet"]["request_ms"] = slim
    return rollup


def goodput(r0: dict, r1: dict) -> Optional[float]:
    """Fleet goodput (served requests/sec) between two rollups, exact
    from the served-counter delta."""
    dt = float(r1.get("ts", 0.0)) - float(r0.get("ts", 0.0))
    if dt <= 0:
        return None
    d = (r1.get("fleet", {}).get("served", 0)
         - r0.get("fleet", {}).get("served", 0))
    return d / dt


class FleetScraper:
    """Builds the target list from a live router (InProc replicas get
    direct-callable targets, replicas advertising a metrics port get
    HTTP targets) and scrapes them all into a federated rollup."""

    def __init__(self, router, clock: Callable[[], float] = time.time):
        self._router = router
        self._clock = clock

    def targets(self) -> List[ScrapeTarget]:
        out: List[ScrapeTarget] = []
        for rid, replica in self._router.replicas():
            port = getattr(replica, "metrics_port", None)
            if port:
                out.append(HttpTarget(rid, "127.0.0.1", port))
            else:
                out.append(InProcTarget(rid, replica))
        return out

    def scrape(self) -> dict:
        payloads = [t.scrape() for t in self.targets()]
        router_stats = router_metrics = None
        try:
            router_stats = self._router.stats()
            router_metrics = self._router.metrics_payload()
        except Exception:          # noqa: BLE001 (rollup survives a closing router)
            pass
        return federate(payloads, router_stats=router_stats,
                        router_metrics=router_metrics,
                        ts=self._clock())


# ---------------------------------------------------------------------------
# durable time-series store
# ---------------------------------------------------------------------------

class TimeSeriesStore:
    """Append-only JSONL ring: records land in ``segment-N.jsonl`` via
    one ``O_APPEND`` write each (a crash can truncate at worst the
    final line — read-back skips torn lines), segments roll over every
    ``seg_records`` records, and only the newest ``seg_keep`` segments
    survive. The manifest (segment ring state) goes through
    :func:`~mxnet_tpu.checkpoint.atomic_writer`, so a crash mid-rollover
    leaves either the old or the new manifest, never a torn one."""

    MANIFEST = "MANIFEST.json"

    def __init__(self, root: Optional[str] = None,
                 seg_records: Optional[int] = None,
                 seg_keep: Optional[int] = None):
        self.root = root or _env.get("MXNET_TPU_OBSWATCH_DIR") \
            or ".obswatch"
        self.seg_records = int(_env.get("MXNET_TPU_OBSWATCH_SEG_RECORDS")
                               if seg_records is None else seg_records)
        self.seg_keep = max(1, int(_env.get("MXNET_TPU_OBSWATCH_SEG_KEEP")
                                   if seg_keep is None else seg_keep))
        self._lock = threading.Lock()
        os.makedirs(self.root, exist_ok=True)
        manifest = self._read_manifest()
        self._seg = int(manifest.get("current", 0))
        self._repair_tail(self._seg_path(self._seg))
        self._count = self._count_records(self._seg_path(self._seg))

    @staticmethod
    def _repair_tail(path: str):
        """Terminate a torn trailing line (crash mid-append) so the
        next O_APPEND record starts a fresh line instead of gluing onto
        the torn one and being lost with it."""
        try:
            with open(path, "rb+") as f:
                f.seek(0, os.SEEK_END)
                if f.tell() > 0:
                    f.seek(-1, os.SEEK_END)
                    if f.read(1) != b"\n":
                        f.write(b"\n")
        except OSError:
            pass

    def _seg_path(self, n: int) -> str:
        return os.path.join(self.root, "segment-%d.jsonl" % n)

    def _manifest_path(self) -> str:
        return os.path.join(self.root, self.MANIFEST)

    def _read_manifest(self) -> dict:
        try:
            with open(self._manifest_path()) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    def _write_manifest(self):
        segs = self.segments()
        data = json.dumps({"current": self._seg, "segments": segs,
                           "seg_records": self.seg_records,
                           "seg_keep": self.seg_keep}).encode()
        with _ckpt.atomic_writer(self._manifest_path()) as f:
            f.write(data)

    @staticmethod
    def _count_records(path: str) -> int:
        try:
            with open(path, "rb") as f:
                return sum(1 for _ in f)
        except OSError:
            return 0

    def segments(self) -> List[int]:
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        for n in names:
            if n.startswith("segment-") and n.endswith(".jsonl"):
                try:
                    out.append(int(n[len("segment-"):-len(".jsonl")]))
                except ValueError:
                    continue
        return sorted(out)

    def append(self, record: dict):
        line = (json.dumps(record) + "\n").encode("utf-8")
        with self._lock:
            if self._count >= self.seg_records:
                self._seg += 1
                self._count = 0
                self._write_manifest()
                for old in self.segments()[:-self.seg_keep]:
                    try:
                        os.unlink(self._seg_path(old))
                    except OSError:
                        pass
            path = self._seg_path(self._seg)
            fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                         0o644)
            try:
                os.write(fd, line)
            finally:
                os.close(fd)
            self._count += 1

    def records(self, t_min: Optional[float] = None,
                t_max: Optional[float] = None) -> List[dict]:
        """Every surviving record in time order; torn trailing lines
        (crash mid-append) are skipped, not fatal."""
        out = []
        for seg in self.segments():
            try:
                with open(self._seg_path(seg)) as f:
                    for line in f:
                        try:
                            rec = json.loads(line)
                        except ValueError:
                            continue
                        ts = rec.get("ts")
                        if t_min is not None and (ts is None or ts < t_min):
                            continue
                        if t_max is not None and (ts is None or ts > t_max):
                            continue
                        out.append(rec)
            except OSError:
                continue
        return out

    def query(self, metric: str, t_min: Optional[float] = None,
              t_max: Optional[float] = None) -> List[Tuple[float, object]]:
        """(ts, value) points for a dotted path into each record
        (``"fleet.p99_ms"``, ``"fleet.served"``); records where the
        path does not resolve are skipped."""
        pts = []
        for rec in self.records(t_min, t_max):
            node: object = rec
            for part in metric.split("."):
                if isinstance(node, dict) and part in node:
                    node = node[part]
                else:
                    node = None
                    break
            if node is not None and not isinstance(node, (dict, list)):
                pts.append((rec.get("ts", 0.0), node))
        return pts


# ---------------------------------------------------------------------------
# multi-window SLO burn rate
# ---------------------------------------------------------------------------

class BurnRateMonitor:
    """Multi-window burn-rate alerting over the federated
    served/breached counters (Google SRE's fast+slow window pattern).

    Burn rate over a window = (bad fraction in window) / error budget,
    where error budget = ``1 - slo_target``. A burn of 1.0 spends the
    budget exactly over the slow period; the alert fires when BOTH the
    fast and the slow window exceed ``threshold`` (fast = reacts in
    seconds, slow = won't page on a blip) with at least ``min_events``
    requests in the fast window. ``budget_spent`` tracks the fraction
    of the slow-period budget already burned since monitoring began, so
    a test can prove the alert beats budget exhaustion."""

    def __init__(self, slo_target: Optional[float] = None,
                 fast_s: Optional[float] = None,
                 slow_s: Optional[float] = None,
                 threshold: Optional[float] = None,
                 min_events: int = 20):
        self.slo_target = float(_env.get("MXNET_TPU_OBSWATCH_SLO_TARGET")
                                if slo_target is None else slo_target)
        self.fast_s = float(_env.get("MXNET_TPU_OBSWATCH_FAST_S")
                            if fast_s is None else fast_s)
        self.slow_s = float(_env.get("MXNET_TPU_OBSWATCH_SLOW_S")
                            if slow_s is None else slow_s)
        self.threshold = float(_env.get("MXNET_TPU_OBSWATCH_BURN")
                               if threshold is None else threshold)
        self.min_events = int(min_events)
        budget = 1.0 - self.slo_target
        if budget <= 0:
            raise MXNetError("slo_target must be < 1.0 (no error budget "
                             "to burn)")
        self._budget = budget
        # (ts, served, breaches) cumulative points
        self._points: List[Tuple[float, float, float]] = []

    def _window_burn(self, window_s: float) -> Tuple[Optional[float], float]:
        """(burn, events) over the trailing window; burn None when the
        window has no baseline or too few events to judge."""
        if len(self._points) < 2:
            return None, 0.0
        t_now, served_now, bad_now = self._points[-1]
        t_cut = t_now - window_s
        base = self._points[0]
        for p in self._points:
            if p[0] <= t_cut:
                base = p
            else:
                break
        d_served = served_now - base[1]
        d_bad = bad_now - base[2]
        if d_served <= 0:
            return None, 0.0
        return (d_bad / d_served) / self._budget, d_served

    def update(self, rollup: dict) -> dict:
        """Feed one federated rollup; returns the burn verdict::

            {"fast_burn", "slow_burn", "budget_spent", "alert"}
        """
        fleet = rollup.get("fleet", {})
        ts = float(rollup.get("ts", 0.0))
        served = float(fleet.get("served", 0))
        bad = float(fleet.get("slo_breaches", 0))
        self._points.append((ts, served, bad))
        # bound memory: nothing older than the slow window matters
        # beyond one baseline point
        t_cut = ts - self.slow_s
        while len(self._points) > 2 and self._points[1][0] <= t_cut:
            self._points.pop(0)
        fast, fast_n = self._window_burn(self.fast_s)
        slow, _ = self._window_burn(self.slow_s)
        t0, s0, b0 = self._points[0]
        d_served = served - s0
        spent = 0.0
        if d_served > 0 and ts > t0:
            overall_bad_frac = (bad - b0) / d_served
            spent = (overall_bad_frac / self._budget) * \
                ((ts - t0) / self.slow_s)
        alert = bool(fast is not None and slow is not None
                     and fast_n >= self.min_events
                     and fast > self.threshold
                     and slow > self.threshold)
        out = {"fast_burn": None if fast is None else round(fast, 4),
               "slow_burn": None if slow is None else round(slow, 4),
               "budget_spent": round(spent, 4), "alert": alert}
        return out


# ---------------------------------------------------------------------------
# the watchtower
# ---------------------------------------------------------------------------

class ObsWatch:
    """Scrape -> federate -> persist -> alert, as one object. Drive it
    manually with :meth:`tick` or let :meth:`start`
    poll every ``MXNET_TPU_OBSWATCH_INTERVAL_MS``. On an alert's rising
    edge it stamps ``slo_burn_alert`` into the step trace (so
    :class:`~mxnet_tpu.tracing.FleetHealthDetector` raises a
    ``fleet_degraded`` anomaly) and its registered ``/healthz`` probe
    reports the burn until it clears."""

    def __init__(self, router, store: Optional[TimeSeriesStore] = None,
                 monitor: Optional[BurnRateMonitor] = None,
                 interval_ms: Optional[float] = None,
                 clock: Callable[[], float] = time.time):
        self._scraper = FleetScraper(router, clock=clock)
        self.store = store if store is not None else TimeSeriesStore()
        self.monitor = monitor if monitor is not None else BurnRateMonitor()
        self.interval_s = float(
            _env.get("MXNET_TPU_OBSWATCH_INTERVAL_MS")
            if interval_ms is None else interval_ms) / 1e3
        self._clock = clock
        self._lock = threading.Lock()
        self._last: Optional[dict] = None
        self._alerting = False
        self._alerts = 0
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._probe_name = "slo_burn:%d" % id(self)
        _tracing.register_health_probe(self._probe_name, self._probe)

    def _probe(self):
        with self._lock:
            if not self._alerting or self._last is None:
                return None
            burn = self._last.get("burn") or {}
        return {"fast_burn": burn.get("fast_burn"),
                "slow_burn": burn.get("slow_burn"),
                "budget_spent": burn.get("budget_spent")}

    def tick(self) -> dict:
        """One scrape+federate+persist+judge cycle; returns the rollup
        (with its burn verdict attached)."""
        rollup = self._scraper.scrape()
        verdict = self.monitor.update(rollup)
        rollup["burn"] = verdict
        rising = False
        with self._lock:
            if verdict["alert"] and not self._alerting:
                rising = True
                self._alerts += 1
            self._alerting = verdict["alert"]
            self._last = rollup
        if rising:
            _log.warning(
                "SLO burn alert: fast=%.2fx slow=%.2fx budget_spent=%.1f%%",
                verdict["fast_burn"], verdict["slow_burn"],
                verdict["budget_spent"] * 100.0)
            _tracing.record_step(0.0, extra={
                "slo_burn_alert": 1,
                "slo_burn_fast": verdict["fast_burn"],
                "slo_burn_slow": verdict["slow_burn"],
                "slo_budget_spent": verdict["budget_spent"],
                "fleet_size": rollup.get("fleet", {}).get("replicas")})
        self.store.append(rollup)
        return rollup

    def rollup(self) -> Optional[dict]:
        with self._lock:
            return self._last

    @property
    def alerts(self) -> int:
        with self._lock:
            return self._alerts

    def start(self) -> "ObsWatch":
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._run,
                                        name="obswatch", daemon=True)
        self._thread.start()
        return self

    def _run(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:      # noqa: BLE001 (poller survives one bad scrape)
                _log.exception("obswatch tick failed")

    def close(self):
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(5.0)
        _tracing.unregister_health_probe(self._probe_name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
