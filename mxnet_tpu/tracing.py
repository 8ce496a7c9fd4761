"""Step traces, anomaly detection, flight recording, live metrics.

``telemetry.py`` gives the framework raw counters/gauges/histograms;
this module is the layer that *interprets* them. The reference had
nothing comparable — a stalled input ring or a mid-run recompile
surfaced as "training got slower" with no artifact saying why. Four
pieces close that gap:

* :class:`StepTrace` — once per training step, snapshots every tracked
  telemetry counter and stores the per-step DELTAS (io stall ms, h2d
  bytes, kvstore traffic, decode-cache hits, executor recompiles)
  alongside the step latency in a bounded ring. Each slow step carries
  the evidence of what it spent its time on.
* Anomaly detectors over that ring — :class:`SlowStepDetector`
  (latency > k x rolling median), :class:`RecompileDetector`
  (``executor.jit_build`` past warmup) and :class:`InputStallDetector`
  (stall-dominated step). A trigger emits a structured event, and with
  ``MXNET_TPU_TRACE_ON_ANOMALY=1`` auto-starts a short, rate-limited
  XLA trace window (:class:`AnomalyProfiler`).
* :class:`FlightRecorder` — ``sys.excepthook`` / ``SIGTERM`` /
  ``SIGUSR1`` handlers that dump the last-N step records, all-thread
  stacks and a full telemetry snapshot into a crash directory for
  post-mortem (``MXNET_TPU_FLIGHT_RECORDER=1``; ``kill -USR1 <pid>``
  dumps without stopping the run).
* :class:`MetricsServer` — a stdlib ``http.server`` thread serving
  Prometheus text format at ``/metrics`` plus ``/healthz`` on
  ``MXNET_TPU_METRICS_PORT``, so an operator (or an obswatch scraper)
  can scrape a live run without attaching to the process. Samples are
  labeled with the worker rank so ``dist_async`` workers are
  distinguishable on one dashboard.

Overhead contract (inherited from telemetry): everything here is off
unless telemetry is enabled; :func:`record_step` and
:func:`maybe_init` start with one flag check and return immediately,
taking no locks and allocating nothing. See docs/performance.md
("Interpreting step traces").
"""
from __future__ import annotations

import http.server
import json
import logging
import math
import os
import signal
import sys
import tempfile
import threading
import time
import traceback
from collections import deque
from typing import Callable, Dict, List, Optional

from . import telemetry as _tel
from . import env as _env

__all__ = ["StepTrace", "SlowStepDetector", "RecompileDetector",
           "InputStallDetector", "SlowRequestDetector",
           "FleetHealthDetector", "LossSpikeDetector",
           "GradExplosionDetector", "DeadUpdateDetector",
           "NonfiniteDetector", "AnomalyProfiler",
           "FlightRecorder", "MetricsServer", "step_trace", "record_step",
           "maybe_init", "set_worker_rank", "worker_rank", "shutdown",
           "register_health_probe", "unregister_health_probe",
           "register_health_info", "unregister_health_info",
           "register_preempt_hook", "unregister_preempt_hook",
           "ensure_flight_recorder"]

_log = logging.getLogger(__name__)

# Per-step delta sources: (record field, telemetry metric, kind).
# "counter" reads the running int; "hist_sum" reads a histogram's
# running sum (the stall histograms observe milliseconds, so their sum
# delta IS the ms this step spent stalled).
DELTA_SOURCES = (
    ("io_stall_ms", "io.pipeline.stall_ms", "hist_sum"),
    ("prefetch_stall_ms", "io.prefetch_stall_ms", "hist_sum"),
    ("feed_stall_ms", "io.feed_stall_ms", "hist_sum"),
    ("h2d_bytes", "ndarray.h2d_bytes", "counter"),
    ("kv_push_bytes", "kvstore.push_bytes", "counter"),
    ("kv_pull_bytes", "kvstore.pull_bytes", "counter"),
    ("decode_cache_hits", "io.decode_cache_hit", "counter"),
    ("recompiles", "executor.jit_build", "counter"),
    ("dispatches", "step.dispatches", "counter"),
    ("fused_recompiles", "step.fused_recompiles", "counter"),
    ("fallbacks", "step.fused_fallback", "counter"),
    ("sanitizer_trips", "sanitizer.trips", "counter"),
    # xprof compile registry: measured XLA compiles this step and the
    # wall time they took (the time_ms histogram's sum delta IS the ms
    # this step spent compiling)
    ("compiles", "compile.count", "counter"),
    ("compile_ms", "compile.time_ms", "hist_sum"),
    # checkpoint manager: snapshots written this step and the wall time
    # they took (checkpoint.py)
    ("ckpt_saves", "ckpt.saves", "counter"),
    ("ckpt_save_ms", "ckpt.save_ms", "hist_sum"),
    # numerics plane (numwatch.py): guard actions taken this step
    ("numwatch_skipped", "numwatch.skipped_steps", "counter"),
    ("numwatch_rolled_back", "numwatch.rollbacks", "counter"),
)

_STALL_FIELDS = ("io_stall_ms", "prefetch_stall_ms", "feed_stall_ms")


# ---------------------------------------------------------------------------
# anomaly detectors
# ---------------------------------------------------------------------------

class SlowStepDetector:
    """Flags a step whose latency exceeds ``k`` x the rolling median of
    the preceding ``window`` steps (after ``warmup`` steps, so compile
    steps don't poison the baseline)."""

    type = "slow_step"

    def __init__(self, k: float = 3.0, warmup: int = 10, window: int = 64):
        self.k = float(k)
        self.warmup = int(warmup)
        self._lat = deque(maxlen=int(window))

    def check(self, rec: dict) -> Optional[dict]:
        lat = rec["latency_ms"]
        prior = sorted(self._lat)
        self._lat.append(lat)
        if rec["step"] <= self.warmup or not prior:
            return None
        median = prior[len(prior) // 2]
        if median > 0 and lat > self.k * median:
            return {"type": self.type, "latency_ms": round(lat, 3),
                    "median_ms": round(median, 3),
                    "ratio": round(lat / median, 2)}
        return None


class RecompileDetector:
    """An ``executor.jit_build`` in steady state means a shape/dtype
    drifted and XLA recompiled mid-run — the silent multi-second stall
    the telemetry tier exists to catch."""

    type = "recompile"

    def __init__(self, warmup: int = 10):
        self.warmup = int(warmup)

    def check(self, rec: dict) -> Optional[dict]:
        n = rec["deltas"].get("recompiles", 0)
        nf = rec["deltas"].get("fused_recompiles", 0)
        nc = rec["deltas"].get("compiles", 0)
        if rec["step"] > self.warmup and (n > 0 or nf > 0 or nc > 0):
            ev = {"type": self.type, "recompiles": n,
                  "latency_ms": round(rec["latency_ms"], 3)}
            if nf:
                # a fused-step retrace past warmup: some batch shape or
                # optimizer structure drifted mid-run (recompile storm)
                ev["fused_recompiles"] = nf
            if nc:
                ev["compiles"] = nc
                ev["compile_ms"] = rec["deltas"].get("compile_ms", 0.0)
            # with the xprof registry armed, name the avals that drifted
            # ("(64,3,224,224)f32 -> (32,...)f32 on batch.data") instead
            # of just flagging that something retraced
            try:
                from . import xprof as _xprof

                cause = _xprof.last_retrace_cause()
            except Exception:
                cause = None
            if cause:
                ev["cause"] = cause
            return ev
        return None


class InputStallDetector:
    """Flags a step that spent more than ``frac`` of its wall time
    blocked on the input pipeline (ring stall + prefetch stall)."""

    type = "input_stall"

    def __init__(self, frac: float = 0.5, min_ms: float = 1.0):
        self.frac = float(frac)
        self.min_ms = float(min_ms)

    def check(self, rec: dict) -> Optional[dict]:
        stall = sum(rec["deltas"].get(f, 0.0) for f in _STALL_FIELDS)
        lat = rec["latency_ms"]
        if stall >= self.min_ms and lat > 0 and stall > self.frac * lat:
            return {"type": self.type, "stall_ms": round(stall, 3),
                    "latency_ms": round(lat, 3),
                    "stall_frac": round(stall / lat, 2)}
        return None


class SlowRequestDetector:
    """Serving-tier SLO guard: fires when a served request batch
    reports a worst-case per-request latency (``request_ms``, stamped
    into the record by ``serving.BatchScheduler``) over the SLO
    (``slo_ms``, stamped from ``MXNET_TPU_SERVE_SLO_MS``). Training
    records never carry ``request_ms``, so this is inert there.

    When the record carries the adaptive scheduler's controller state
    (``adaptive_wait_ms``, ``queue_depth``) the event copies it, so a
    breached SLO is attributable at a glance: a wide wait means the
    controller was still coalescing, a deep queue means overload. When
    the distributed tracer sampled the offending request the record
    also carries ``worst_trace_id``; copying it into the event links
    the anomaly straight to a kept span tree
    (``tools/trace_report.py --view waterfall <id>``)."""

    type = "slow_request"

    def check(self, rec: dict) -> Optional[dict]:
        req = rec.get("request_ms")
        slo = rec.get("slo_ms")
        if req is not None and slo and req > slo:
            ev = {"type": self.type, "request_ms": round(req, 3),
                  "slo_ms": round(float(slo), 3),
                  "over_frac": round(req / slo - 1.0, 3)}
            for k in ("adaptive_wait_ms", "queue_depth",
                      "worst_trace_id"):
                if rec.get(k) is not None:
                    ev[k] = rec[k]
            return ev
        return None


class FleetHealthDetector:
    """Fleet-tier guard: the :class:`~mxnet_tpu.fleet.FleetRouter`'s
    monitor stamps ``fleet_down`` (dead replicas awaiting respawn) and
    ``breaker_open`` (replicas currently shedding load) into a step
    record whenever either is nonzero; this turns that into an anomaly
    so /healthz and the flight recorder see a shrinking fleet the same
    way they see a slow request. Inert for training and single-replica
    serving records."""

    type = "fleet_degraded"

    def check(self, rec: dict) -> Optional[dict]:
        down = rec.get("fleet_down", 0)
        tripped = rec.get("breaker_open", 0)
        burn = rec.get("slo_burn_alert", 0)
        if down or tripped or burn:
            ev = {"type": self.type}
            if down:
                ev["replicas_down"] = int(down)
            if tripped:
                ev["breakers_open"] = int(tripped)
            if burn:
                # stamped by obswatch's burn-rate monitor: both the
                # fast and slow windows are burning error budget past
                # the alert threshold
                ev["slo_burn_alert"] = 1
                for k in ("slo_burn_fast", "slo_burn_slow",
                          "slo_budget_spent"):
                    if rec.get(k) is not None:
                        ev[k] = round(float(rec[k]), 4)
            if rec.get("fleet_size") is not None:
                ev["fleet_size"] = int(rec["fleet_size"])
            return ev
        return None


class LossSpikeDetector:
    """Numerics-plane guard: numwatch's cadence fetch stamps the
    in-graph loss (``numwatch_loss``) into the step record; a loss more
    than MXNET_TPU_NUMWATCH_SPIKE_K times its rolling median is a
    spike — bad batch, lr too hot, or the first visible symptom of a
    numeric blowup. Inert on records without the stamp (numwatch off,
    or an off-cadence step)."""

    type = "loss_spike"

    def __init__(self, k: Optional[float] = None, window: int = 32):
        self.k = float(k if k is not None
                       else _env.get("MXNET_TPU_NUMWATCH_SPIKE_K"))
        self._hist: deque = deque(maxlen=window)

    def check(self, rec: dict) -> Optional[dict]:
        loss = rec.get("numwatch_loss")
        if loss is None or not math.isfinite(loss):
            return None
        prior = sorted(self._hist)
        self._hist.append(float(loss))
        if len(prior) < 3:
            return None
        median = prior[len(prior) // 2]
        if median > 0 and loss > self.k * median:
            return {"type": self.type, "loss": round(float(loss), 6),
                    "median": round(median, 6),
                    "ratio": round(float(loss) / median, 2)}
        return None


class GradExplosionDetector:
    """Numerics-plane guard over the fetched global gradient norm
    (``numwatch_grad_norm``): a norm more than
    MXNET_TPU_NUMWATCH_EXPLODE_K times its rolling median means the
    backward pass is exploding — the classic precursor of the NaN the
    NonfiniteDetector would report a few steps later."""

    type = "grad_explosion"

    def __init__(self, k: Optional[float] = None, window: int = 32):
        self.k = float(k if k is not None
                       else _env.get("MXNET_TPU_NUMWATCH_EXPLODE_K"))
        self._hist: deque = deque(maxlen=window)

    def check(self, rec: dict) -> Optional[dict]:
        norm = rec.get("numwatch_grad_norm")
        if norm is None or not math.isfinite(norm):
            return None
        prior = sorted(self._hist)
        self._hist.append(float(norm))
        if len(prior) < 3:
            return None
        median = prior[len(prior) // 2]
        if median > 0 and norm > self.k * median:
            return {"type": self.type,
                    "grad_norm": round(float(norm), 6),
                    "median": round(median, 6),
                    "ratio": round(float(norm) / median, 2)}
        return None


class DeadUpdateDetector:
    """Numerics-plane guard over the largest per-tensor update-to-
    weight ratio (``numwatch_uw_max``): gradients flowing but every
    update below MXNET_TPU_NUMWATCH_DEAD_UW means training is inert —
    an lr schedule that collapsed to zero, a saturated optimizer state,
    or a frozen graph."""

    type = "dead_update"

    def __init__(self, threshold: Optional[float] = None):
        self.threshold = float(
            threshold if threshold is not None
            else _env.get("MXNET_TPU_NUMWATCH_DEAD_UW"))

    def check(self, rec: dict) -> Optional[dict]:
        uw = rec.get("numwatch_uw_max")
        if uw is None:
            return None
        norm = rec.get("numwatch_grad_norm") or 0.0
        if uw < self.threshold and norm > 0 and math.isfinite(norm):
            return {"type": self.type, "uw_max": float(uw),
                    "grad_norm": round(float(norm), 6),
                    "threshold": self.threshold}
        return None


class NonfiniteDetector:
    """Numerics-plane alarm: any nonfinite param or grad element seen
    by the fetch (``numwatch_nonfinite``) becomes an anomaly event
    carrying the provenance verdict (``numwatch_bad_tensor`` — the
    first tensor to go bad, in forward order) and the guard counters,
    so a crash dump names the layer, not just the symptom."""

    type = "nonfinite"

    def check(self, rec: dict) -> Optional[dict]:
        n = rec.get("numwatch_nonfinite")
        if not n:
            return None
        ev = {"type": self.type, "nonfinite": int(n)}
        for k in ("numwatch_bad_tensor", "numwatch_skips",
                  "numwatch_rollbacks"):
            if rec.get(k) is not None:
                ev[k.replace("numwatch_", "")] = rec[k]
        return ev


def default_detectors() -> list:
    return [SlowStepDetector(), RecompileDetector(), InputStallDetector(),
            SlowRequestDetector(), FleetHealthDetector(),
            LossSpikeDetector(), GradExplosionDetector(),
            DeadUpdateDetector(), NonfiniteDetector()]


# ---------------------------------------------------------------------------
# pluggable /healthz probes
# ---------------------------------------------------------------------------

_probe_lock = threading.Lock()
_health_probes: Dict[str, object] = {}


def register_health_probe(name: str, probe):
    """Register a liveness probe consulted by ``/healthz``: a callable
    returning None when healthy or a JSON-able failure detail when not.
    Any failing probe flips the endpoint to ``{"status": "degraded"}``
    with HTTP 503 — the serving tier registers its SLO check here so a
    load balancer drains a replica whose tail latency broke the SLO."""
    with _probe_lock:
        _health_probes[name] = probe


def unregister_health_probe(name: str):
    with _probe_lock:
        _health_probes.pop(name, None)


# identity/info providers: merged into the /healthz JSON regardless of
# health (probes above only surface when they FAIL; info is always on)
_health_info: Dict[str, object] = {}


def register_health_info(name: str, info):
    """Register an identity/info provider for ``/healthz``: a callable
    returning a JSON-able dict merged into the payload on every scrape
    (existing payload keys win). The serving tier registers its
    in-flight/served counts here so the fleet router and a human curl
    read one replica-identity signal."""
    with _probe_lock:
        _health_info[name] = info


def unregister_health_info(name: str):
    with _probe_lock:
        _health_info.pop(name, None)


def _run_health_info() -> Dict[str, object]:
    """Merged info payload ({} when none registered). A provider that
    raises contributes an error string instead of crashing the scrape."""
    with _probe_lock:
        infos = list(_health_info.items())
    merged: Dict[str, object] = {}
    for name, info in infos:
        try:
            detail = info()
            if detail:
                merged.update(dict(detail))
        except Exception as e:
            merged[name] = "info provider raised: %s" % (e,)
    return merged


def _run_health_probes() -> Dict[str, object]:
    """Failing probes by name ({} == healthy). A probe that raises is
    itself a failure — a broken health check must not read as green."""
    with _probe_lock:
        probes = list(_health_probes.items())
    failing = {}
    for name, probe in probes:
        try:
            detail = probe()
        except Exception as e:
            detail = "probe raised: %s" % (e,)
        if detail is not None:
            failing[name] = detail
    return failing


# ---------------------------------------------------------------------------
# anomaly-triggered profiling
# ---------------------------------------------------------------------------

class AnomalyProfiler:
    """Starts a short XLA trace window when an anomaly fires, so the
    evidence for a slow step is captured while it is still happening.

    Rate-limited: at most one window per ``cooldown_s`` (suppressed
    triggers are counted, not traced), and never while a capture —
    auto or user-started — is already running. ``start_fn``/``stop_fn``
    default to :func:`mxnet_tpu.profiler.start`/``stop`` and exist so
    tests can observe the windowing without a real jax trace."""

    def __init__(self, trace_dir: Optional[str] = None,
                 window_steps: Optional[int] = None,
                 cooldown_s: Optional[float] = None,
                 start_fn: Optional[Callable] = None,
                 stop_fn: Optional[Callable] = None):
        self.trace_dir = trace_dir or _env.get(
            "MXNET_TPU_TRACE_DIR",
            default=os.path.join(tempfile.gettempdir(),
                                 "mxnet_tpu_anomaly_trace"))
        self.window_steps = int(window_steps if window_steps is not None
                                else _env.get("MXNET_TPU_TRACE_WINDOW"))
        self.cooldown_s = float(cooldown_s if cooldown_s is not None
                                else _env.get("MXNET_TPU_TRACE_COOLDOWN"))
        self._start_fn = start_fn
        self._stop_fn = stop_fn
        self._last_start: Optional[float] = None
        self._stop_at: Optional[int] = None
        self.started = 0
        self.suppressed = 0

    def _start(self, path: str):
        if self._start_fn is not None:
            return self._start_fn(path)
        from . import profiler as _prof

        _prof.start(path)

    def _stop(self):
        if self._stop_fn is not None:
            return self._stop_fn()
        from . import profiler as _prof

        _prof.stop()

    def on_anomaly(self, step: int, event: dict) -> bool:
        """Maybe open a trace window for ``event``; True if started."""
        if self._stop_at is not None:
            return False
        if self._start_fn is None:
            from . import profiler as _prof

            if _prof.is_running():   # user capture in progress: stay out
                return False
        now = time.monotonic()
        if self._last_start is not None \
                and now - self._last_start < self.cooldown_s:
            self.suppressed += 1
            _tel.inc("tracing.auto_trace_suppressed")
            return False
        path = os.path.join(self.trace_dir,
                            "step%d_%s" % (step, event["type"]))
        try:
            os.makedirs(path, exist_ok=True)
            self._start(path)
        except Exception as e:
            _log.warning("anomaly trace start failed: %s", e)
            return False
        self._last_start = now
        self._stop_at = step + self.window_steps
        self.started += 1
        _tel.inc("tracing.auto_traces")
        _log.warning("anomaly at step %d (%s): capturing %d-step trace "
                     "into %s", step, event["type"], self.window_steps, path)
        return True

    def on_step(self, step: int):
        """Close the window once ``window_steps`` more steps elapsed."""
        if self._stop_at is not None and step >= self._stop_at:
            self._stop_at = None
            try:
                self._stop()
            except Exception as e:
                _log.warning("anomaly trace stop failed: %s", e)


# ---------------------------------------------------------------------------
# step trace recorder
# ---------------------------------------------------------------------------

class StepTrace:
    """Bounded ring of per-step records, each carrying the telemetry
    deltas accumulated during that step.

    ``record(latency_ms)`` is called once per training step (the fit
    loop). The baseline for step 1's deltas is the
    counter state at construction, so a recorder created at fit() start
    attributes everything to steps."""

    def __init__(self, capacity: Optional[int] = None, detectors=None,
                 profiler: Optional[AnomalyProfiler] = None,
                 event_cooldown: Optional[int] = None):
        cap = int(capacity if capacity is not None
                  else _env.get("MXNET_TPU_TRACE_RING"))
        self._ring: deque = deque(maxlen=max(1, cap))
        self._lock = threading.Lock()
        self._step = 0
        self._prev = self._raw_values()
        self.detectors = (default_detectors() if detectors is None
                          else list(detectors))
        if profiler is None and _env.get("MXNET_TPU_TRACE_ON_ANOMALY"):
            profiler = AnomalyProfiler()
        self.profiler = profiler
        self.events: deque = deque(maxlen=256)
        self.event_cooldown = int(
            event_cooldown if event_cooldown is not None
            else _env.get("MXNET_TPU_TRACE_EVENT_COOLDOWN"))
        self._last_event_step: Dict[str, int] = {}

    @staticmethod
    def _raw_values() -> Dict[str, float]:
        return {field: _tel.peek(metric, kind) or 0
                for field, metric, kind in DELTA_SOURCES}

    @staticmethod
    def _dominant(deltas: Dict[str, float], latency_ms: float) -> str:
        """Label the step with what it spent its time on: a measured
        compile (xprof registry) or a recompile trumps everything (it
        IS the latency), then whichever stall source claims >25% of
        the wall time; otherwise compute."""
        if deltas.get("compiles", 0) > 0:
            # xprof measured the compile itself — the most specific
            # label available (its CompileRecord carries the cause)
            return "compile"
        if deltas.get("recompiles", 0) > 0 \
                or deltas.get("fused_recompiles", 0) > 0:
            return "recompile"
        stalls = [(deltas.get(f, 0.0), f) for f in _STALL_FIELDS]
        worst, field = max(stalls)
        if latency_ms > 0 and worst > 0.25 * latency_ms:
            return field
        return "compute"

    def record(self, latency_ms: float, extra: Optional[dict] = None) -> dict:
        """Snapshot counters, compute deltas vs the previous step, run
        the detectors; returns the appended record."""
        raw = self._raw_values()
        with self._lock:
            self._step += 1
            step = self._step
            deltas = {}
            for field, _metric, kind in DELTA_SOURCES:
                d = raw[field] - self._prev.get(field, 0)
                if kind == "hist_sum":
                    deltas[field] = round(d, 3)
                else:
                    deltas[field] = int(d)
            self._prev = raw
            rec = {"step": step, "ts": round(time.time(), 6),
                   "latency_ms": round(float(latency_ms), 3),
                   "deltas": deltas,
                   "dominant": self._dominant(deltas, latency_ms)}
            if extra:
                rec.update(extra)
            self._ring.append(rec)
        if self.profiler is not None:
            self.profiler.on_step(step)
        for det in self.detectors:
            try:
                ev = det.check(rec)
            except Exception as e:
                _log.warning("anomaly detector %s failed: %s",
                             type(det).__name__, e)
                continue
            if ev is None:
                continue
            last = self._last_event_step.get(ev["type"])
            if last is not None and step - last < self.event_cooldown:
                continue
            self._last_event_step[ev["type"]] = step
            ev.update(step=step, ts=rec["ts"], dominant=rec["dominant"])
            self.events.append(ev)
            _tel.inc("tracing.anomalies")
            _tel.inc("tracing.anomaly.%s" % ev["type"])
            _log.warning("step %d anomaly %s: %s", step, ev["type"],
                         {k: v for k, v in ev.items()
                          if k not in ("type", "step", "ts")})
            if self.profiler is not None:
                if self.profiler.on_anomaly(step, ev):
                    ev["trace_started"] = True
        return rec

    @property
    def step(self) -> int:
        return self._step

    def records(self) -> List[dict]:
        with self._lock:
            return list(self._ring)

    def dump_jsonl(self, path: str) -> int:
        """Write the ring, one record per line; returns record count."""
        recs = self.records()
        with open(path, "w") as f:
            for rec in recs:
                f.write(json.dumps(rec) + "\n")
        return len(recs)

    def reset(self):
        with self._lock:
            self._ring.clear()
            self._step = 0
            self._prev = self._raw_values()
            self.events.clear()
            self._last_event_step.clear()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def _format_all_stacks() -> str:
    """Every thread's current stack (the post-mortem "where was
    everyone" view: a wedged ring consumer, a dead heartbeat thread)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for tid, frame in sys._current_frames().items():
        out.append("Thread %s (%d):" % (names.get(tid, "?"), tid))
        out.extend(l.rstrip() for l in traceback.format_stack(frame))
        out.append("")
    return "\n".join(out)


# Preemption hooks: callables run from the SIGTERM handler before the
# signal is re-raised (signal-handler context: keep them short and
# non-blocking). A hook may return the string "defer" to suppress the
# immediate re-raise — the deferring component owns termination from
# that point and must re-deliver SIGTERM itself once it is safe (the
# checkpoint manager does this at the next step boundary, where the
# donated packs are whole). Hook exceptions are swallowed: a broken
# hook must not mask the preemption.
_preempt_hooks: List[Callable[[], Optional[str]]] = []
_preempt_lock = threading.Lock()


def register_preempt_hook(fn: Callable[[], Optional[str]]):
    """Run ``fn()`` on SIGTERM before default termination proceeds."""
    with _preempt_lock:
        if fn not in _preempt_hooks:
            _preempt_hooks.append(fn)
    return fn


def unregister_preempt_hook(fn: Callable[[], Optional[str]]):
    with _preempt_lock:
        try:
            _preempt_hooks.remove(fn)
        except ValueError:
            pass


def _run_preempt_hooks() -> bool:
    """Returns True when any hook asked to defer termination."""
    with _preempt_lock:
        hooks = list(_preempt_hooks)
    defer = False
    for fn in hooks:
        try:
            if fn() == "defer":
                defer = True
        except Exception as e:
            try:
                _log.error("preempt hook %r failed: %s", fn, e)
            except Exception:
                pass
    return defer


class FlightRecorder:
    """Dumps the step ring + all-thread stacks + telemetry snapshot
    into a crash directory on unhandled exception, SIGTERM (preemption)
    or SIGUSR1 (operator-requested, run continues).

    ``install()`` chains the previous ``sys.excepthook`` and signal
    handlers; SIGTERM runs the registered preemption hooks and then
    re-raises so the process still terminates with default semantics —
    unless a hook deferred, in which case that hook's owner re-delivers
    the signal itself at the next safe point."""

    def __init__(self, crash_dir: Optional[str] = None, trace=None):
        self.crash_dir = crash_dir or _env.get(
            "MXNET_TPU_CRASH_DIR",
            default=os.path.join(tempfile.gettempdir(), "mxnet_tpu_crash"))
        self._trace = trace
        self._installed = False
        self._prev_excepthook = None
        self._prev_handlers: Dict[int, object] = {}
        self._dump_count = 0

    def _ring(self):
        if self._trace is not None:
            return self._trace
        return _recorder   # the global recorder, if one exists

    def dump(self, reason: str, exc_info=None) -> Optional[str]:
        """Write one dump directory; never raises (a broken disk must
        not mask the original failure). Returns the path or None."""
        try:
            self._dump_count += 1
            d = os.path.join(
                self.crash_dir, "flight-%s-pid%d-%d"
                % (time.strftime("%Y%m%dT%H%M%S"), os.getpid(),
                   self._dump_count))
            os.makedirs(d, exist_ok=True)
            tr = self._ring()
            meta = {"reason": reason, "ts": round(time.time(), 6),
                    "pid": os.getpid(), "rank": worker_rank(),
                    "argv": list(sys.argv),
                    "steps_recorded": tr.step if tr is not None else 0,
                    "events": list(tr.events) if tr is not None else []}
            if exc_info is not None and exc_info[0] is not None:
                meta["exception"] = "".join(
                    traceback.format_exception(*exc_info))
            with open(os.path.join(d, "meta.json"), "w") as f:
                json.dump(meta, f, indent=1)
            with open(os.path.join(d, "stacks.txt"), "w") as f:
                f.write(_format_all_stacks())
            with open(os.path.join(d, "telemetry.json"), "w") as f:
                json.dump(_tel.snapshot(), f, indent=1)
            if tr is not None:
                tr.dump_jsonl(os.path.join(d, "steps.jsonl"))
            # last-K model-health rows from the numerics plane, so a
            # post-mortem shows the numeric trajectory into the failure
            try:
                from . import numwatch as _numwatch

                rows = _numwatch.health_rows()
                if rows:
                    with open(os.path.join(d, "numwatch.jsonl"),
                              "w") as f:
                        for row in rows:
                            f.write(json.dumps(row) + "\n")
            except Exception:
                pass
            _log.error("flight recorder dump (%s) written to %s", reason, d)
            return d
        except Exception as e:
            try:
                _log.error("flight recorder dump failed: %s", e)
            except Exception:
                pass
            return None

    # -- hook installation -------------------------------------------------
    def install(self):
        if self._installed:
            return self
        self._prev_excepthook = sys.excepthook
        sys.excepthook = self._excepthook
        for sig in (signal.SIGTERM, signal.SIGUSR1):
            try:
                self._prev_handlers[sig] = signal.signal(sig, self._on_signal)
            except (ValueError, OSError):
                # not the main thread / unsupported platform: exception
                # and explicit dump() paths still work
                pass
        self._installed = True
        return self

    def uninstall(self):
        if not self._installed:
            return
        if sys.excepthook is self._excepthook:
            sys.excepthook = self._prev_excepthook
        for sig, prev in self._prev_handlers.items():
            try:
                signal.signal(sig, prev if prev is not None
                              else signal.SIG_DFL)
            except (ValueError, OSError):
                pass
        self._prev_handlers.clear()
        self._installed = False

    def _excepthook(self, etype, value, tb):
        self.dump("exception:%s" % etype.__name__, (etype, value, tb))
        prev = self._prev_excepthook or sys.__excepthook__
        prev(etype, value, tb)

    def _on_signal(self, signum, frame):
        try:
            name = signal.Signals(signum).name
        except ValueError:
            name = str(signum)
        self.dump("signal:%s" % name)
        if signum == signal.SIGTERM:
            if _run_preempt_hooks():
                # a hook deferred termination (e.g. the checkpoint
                # manager is mid-step and will save at the next step
                # boundary, then re-deliver SIGTERM itself)
                return
            # restore the prior disposition and re-raise so termination
            # proceeds exactly as it would have without us
            prev = self._prev_handlers.get(signum)
            try:
                signal.signal(signum, prev if prev is not None
                              else signal.SIG_DFL)
            except (ValueError, OSError):
                pass
            os.kill(os.getpid(), signum)
        # SIGUSR1: dump-and-continue


# ---------------------------------------------------------------------------
# live metrics exposition
# ---------------------------------------------------------------------------

def _prom_name(name: str) -> str:
    out = []
    for ch in name:
        out.append(ch if ch.isalnum() or ch == "_" else "_")
    return "mxnet_tpu_" + "".join(out)


def _prom_label_value(v) -> str:
    """Escape a label value per the exposition format: backslash, double
    quote and newline must be escaped or standard scrapers reject the
    whole page."""
    return (str(v).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _prom_labels(**labels) -> str:
    """``{k="v",...}`` with escaped values, keys in the given order."""
    return "{%s}" % ",".join('%s="%s"' % (k, _prom_label_value(v))
                             for k, v in labels.items())


def _fmt_le(bound: float) -> str:
    """Prometheus convention: integral bounds print without the
    trailing ``.0`` (``le="10"``, not ``le="10.0"``)."""
    return "%g" % bound


def prometheus_text() -> str:
    """The full registry in the Prometheus text exposition format
    (version 0.0.4). Counters/gauges map directly; histograms emit real
    ``_bucket`` series with cumulative ``le`` labels (closing with
    ``+Inf``) plus exact ``_sum``/``_count``, so a standard scraper or
    the obswatch federator can bucket-merge across replicas. Every
    sample carries the worker rank label; label values are escaped."""
    rank = worker_rank()
    lbl = _prom_labels(rank=rank)
    lines = []
    for name, m in _tel.metrics_items():
        pname = _prom_name(name)
        if isinstance(m, _tel.Counter):
            lines.append("# TYPE %s counter" % pname)
            lines.append("%s%s %d" % (pname, lbl, m.value))
        elif isinstance(m, _tel.Gauge):
            lines.append("# TYPE %s gauge" % pname)
            lines.append("%s%s %s" % (pname, lbl, repr(m.value)))
        elif isinstance(m, _tel.Histogram):
            ex = m.export()
            count = ex.get("count", 0)
            buckets = ex.get("buckets") or {}
            lines.append("# TYPE %s histogram" % pname)
            for bound, cum in zip(buckets.get("bounds", ()),
                                  buckets.get("counts", ())):
                lines.append("%s_bucket%s %d"
                             % (pname,
                                _prom_labels(rank=rank, le=_fmt_le(bound)),
                                cum))
            lines.append("%s_bucket%s %d"
                         % (pname, _prom_labels(rank=rank, le="+Inf"),
                            count))
            lines.append("%s_sum%s %s" % (pname, lbl, repr(ex.get("sum", 0))))
            lines.append("%s_count%s %d" % (pname, lbl, count))
    return "\n".join(lines) + "\n"


class _MetricsHandler(http.server.BaseHTTPRequestHandler):
    server_version = "mxnet-tpu-metrics/1"

    def do_GET(self):   # noqa: N802 (http.server API)
        if self.path.split("?")[0] == "/metrics":
            body = prometheus_text().encode()
            ctype = "text/plain; version=0.0.4; charset=utf-8"
        elif self.path.split("?")[0] == "/healthz":
            tr = _recorder
            failing = _run_health_probes()
            payload = {
                "status": "degraded" if failing else "ok",
                "pid": os.getpid(),
                "rank": worker_rank(),
                "uptime_s": round(time.time() - self.server.started_at, 3),
                "steps": tr.step if tr is not None else 0,
                "anomalies": len(tr.events) if tr is not None else 0,
            }
            for k, v in _run_health_info().items():
                payload.setdefault(k, v)
            if failing:
                payload["probes"] = failing
            body = json.dumps(payload).encode()
            ctype = "application/json"
            if failing:
                # 503 so a load balancer health check drains the
                # replica without parsing the JSON
                self.send_response(503)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
        else:
            self.send_error(404)
            return
        self.send_response(200)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, fmt, *args):   # scrapes must not spam stderr
        _log.debug("metrics server: " + fmt, *args)


class MetricsServer:
    """Threaded HTTP server for `/metrics` + `/healthz`; port 0 binds
    an ephemeral port (tests), exposed as ``.port``."""

    def __init__(self, port: int, host: str = ""):
        self._httpd = http.server.ThreadingHTTPServer(
            (host, int(port)), _MetricsHandler)
        self._httpd.daemon_threads = True
        self._httpd.started_at = time.time()
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="mxtpu-metrics",
            daemon=True)
        self._thread.start()

    def stop(self):
        """Shut down the HTTP server AND join its serve thread: after
        this returns no ``mxtpu-metrics`` thread is alive (the
        thread/process-leak fixture in tests/conftest.py depends on
        that). Idempotent."""
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except Exception:
            pass
        th = self._thread
        if th is not None:
            self._thread = None
            th.join(timeout=5.0)
            if th.is_alive():
                _log.warning("MetricsServer.stop: serve thread still "
                             "alive after 5s join; leaking the (daemon) "
                             "thread rather than hanging teardown")

    # historical name, kept for callers that treat this like a file
    close = stop


# ---------------------------------------------------------------------------
# process-global wiring
# ---------------------------------------------------------------------------

_init_lock = threading.Lock()
_recorder: Optional[StepTrace] = None
_metrics_server: Optional[MetricsServer] = None
_flight_recorder: Optional[FlightRecorder] = None
_watchdog = None                 # sanitizers.DeadlockWatchdog
_atexit_registered = False
_worker_rank = int(os.environ.get("MXTPU_WORKER_RANK", "0") or 0)


def set_worker_rank(rank: int):
    """Tag exported metrics with this process's worker rank (called by
    ``kvstore.create`` so dist runs are distinguishable per-worker)."""
    global _worker_rank
    _worker_rank = int(rank)


def worker_rank() -> int:
    return _worker_rank


def step_trace() -> StepTrace:
    """The process-global step recorder (created on first use)."""
    global _recorder
    if _recorder is None:
        with _init_lock:
            if _recorder is None:
                _recorder = StepTrace()
    return _recorder


def record_step(latency_ms: float, extra: Optional[dict] = None):
    """Fit-loop hook: record one step into the global ring. No-op
    (one flag check) while telemetry is disabled."""
    if not _tel._ENABLED:
        return None
    return step_trace().record(latency_ms, extra)


def maybe_init():
    """Env-driven one-shot setup, called at fit() entry: start
    the metrics server when ``MXNET_TPU_METRICS_PORT`` is set, install
    the flight recorder when ``MXNET_TPU_FLIGHT_RECORDER=1``, start
    the deadlock watchdog when ``MXNET_TPU_SANITIZE`` includes
    ``deadlock``. Registers :func:`shutdown` with atexit on first use,
    so a fit() that never reaches explicit teardown still stops the
    server/watchdog threads. Idempotent; one flag check while
    telemetry is disabled."""
    if not _tel._ENABLED:
        return None
    global _metrics_server, _flight_recorder, _watchdog, \
        _atexit_registered
    with _init_lock:
        port = _env.get("MXNET_TPU_METRICS_PORT")
        if _metrics_server is None and port:
            try:
                _metrics_server = MetricsServer(int(port))
                _log.info("metrics server listening on :%d (/metrics, "
                          "/healthz)", _metrics_server.port)
            except (OSError, ValueError) as e:
                _log.warning("metrics server failed to start on %r: %s",
                             port, e)
        if _flight_recorder is None \
                and _env.get("MXNET_TPU_FLIGHT_RECORDER"):
            _flight_recorder = FlightRecorder().install()
        if _watchdog is None:
            from .analysis import sanitizers as _san
            if _san.enabled("deadlock"):
                _watchdog = _san.DeadlockWatchdog().start()
                _log.info("deadlock watchdog armed (threshold %.0fs)",
                          _watchdog._threshold)
        if not _atexit_registered:
            import atexit
            atexit.register(shutdown)
            _atexit_registered = True
    return _metrics_server


def metrics_server() -> Optional[MetricsServer]:
    return _metrics_server


def flight_recorder() -> Optional[FlightRecorder]:
    return _flight_recorder


def ensure_flight_recorder() -> FlightRecorder:
    """Install the global flight recorder even when the
    ``MXNET_TPU_FLIGHT_RECORDER`` env flag is off. The checkpoint
    manager's SIGTERM grace path needs its signal routing (preempt
    hooks run from ``_on_signal``) regardless of whether the operator
    asked for crash dumps. Registers :func:`shutdown` with atexit so
    the handlers are uninstalled on interpreter exit."""
    global _flight_recorder, _atexit_registered
    with _init_lock:
        if _flight_recorder is None:
            _flight_recorder = FlightRecorder().install()
        if not _atexit_registered:
            import atexit
            atexit.register(shutdown)
            _atexit_registered = True
        return _flight_recorder


def shutdown():
    """Tear down global state (tests / end of run / atexit): stop the
    server (joining its thread), stop the watchdog, uninstall
    flight-recorder hooks, drop the recorder. Idempotent."""
    global _recorder, _metrics_server, _flight_recorder, _watchdog
    with _init_lock:
        server, _metrics_server = _metrics_server, None
        watchdog, _watchdog = _watchdog, None
        if _flight_recorder is not None:
            _flight_recorder.uninstall()
            _flight_recorder = None
        _recorder = None
    # join threads OUTSIDE _init_lock: the watchdog's progress probe
    # takes _init_lock via step_trace(), so joining it under the lock
    # would stall shutdown until the join timeout
    if server is not None:
        server.stop()
    if watchdog is not None:
        watchdog.stop()
