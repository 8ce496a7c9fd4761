"""Continuous-batching inference serving tier.

The reference framework stopped at a predict-only C ABI (one
synchronous forward per caller); this module is the throughput/latency
path the ROADMAP's "millions of users" north star actually needs. It
composes pieces that already exist — the single-dispatch
:class:`~mxnet_tpu.fused_step.FusedInfer` executable, the ``dp`` device
mesh + NamedSharding batch placement from the executor group, the xprof
compile registry and the Prometheus :class:`~mxnet_tpu.tracing.MetricsServer`
— into three layers:

* :class:`BatchScheduler` — a deadline-aware continuous batcher.
  Every request carries a ``priority`` lane (interactive/batch) and a
  ``deadline_ms`` (explicit, or derived from the SLO); the dispatch
  decision is driven by the earliest deadline in the queue — dispatch
  immediately when any pending request's slack (deadline minus the
  rolling service-time estimate) is about to run out, otherwise keep
  coalescing toward the next bucket rung. A closed-loop
  :class:`AdaptiveWaitController` replaces the fixed ``max_wait_ms``:
  it reads the sliding-window SLO probe and an EWMA arrival-rate
  estimator, widening the coalescing window while p99 headroom exists
  (filling bigger buckets) and collapsing it when the probe nears
  breach. Every dispatched batch is padded up to a small ladder of
  bucket sizes (default powers of two), so mixed request rates compile
  at most ``len(buckets)`` executables EVER and steady state runs
  retrace-free at exactly one XLA dispatch per served batch. Under
  overload the scheduler sheds the lowest-priority, most-expired
  requests with a typed :class:`RequestShed` error instead of
  convoying every queued request past the SLO.
* :class:`InferenceServer` — wires a bound Module to a FusedInfer
  (params packed once, replicated across the mesh; request batches
  sharded along ``dp``), owns the scheduler, exports `/metrics` +
  `/healthz` (including the controller state: adaptive wait, queue
  depth, arrival rate), and registers the SLO health probe: when the
  sliding-window p99 exceeds ``MXNET_TPU_SERVE_SLO_MS``, `/healthz`
  flips to ``degraded`` (HTTP 503) and a ``slow_request`` anomaly
  fires through the step-trace detectors.
* latency decomposition — every request's wall time splits exactly
  into intake wait / scheduler hold / H2D+pad / dispatch / D2H
  (``serve.queue_ms``, ``serve.sched_idle_ms``, ``serve.h2d_ms``,
  ``serve.dispatch_ms``, ``serve.d2h_ms``; the five sum to
  ``serve.request_ms`` per request, pinned by test) with p50/p99
  exported through the metrics server and summarized by
  ``trace_report --view serve``. ``serve.pad_waste_ms`` stays an
  overlay (dispatch time × padded fraction), not a wall-time term.

Shutdown contract: ``close()`` stops intake, DRAINS every queued
request (each gets a result or an error — nothing hangs a caller), and
joins the worker thread; the tests' thread/process leak gate holds.

No benchmark cell drives this yet: requests/sec, goodput at SLO and
tail latency on the chip are not measured (PERF.md section 7 rows 1-2
name the cells that will).
"""
from __future__ import annotations

import collections
import logging
import queue as _queue
import threading
import time
import uuid
from typing import List, Optional, Sequence, Tuple

import numpy as np

from . import dtrace as _dtrace
from . import env as _env
from . import faults as _faults
from . import telemetry as _tel
from . import tracing as _tracing
from .base import MXNetError
from .io_pipeline import RequestStager

__all__ = ["bucket_ladder", "LANES", "Request", "RequestShed",
           "ArrivalRateEstimator", "ServiceTimeEstimator",
           "AdaptiveWaitController", "BatchScheduler", "InferenceServer"]

_log = logging.getLogger(__name__)


def _corr_ids(reqs, cap: int = 8) -> str:
    """Correlation ids for a server-side log line: request ids, each
    with its trace id when the request rode in sampled — a
    client-reported failure greps straight to the server event."""
    parts = []
    for r in list(reqs)[:cap]:
        ctx = getattr(r, "trace_ctx", None)
        parts.append("%s(trace=%s)" % (r.request_id, ctx["t"])
                     if ctx else r.request_id)
    if len(reqs) > cap:
        parts.append("... +%d more" % (len(reqs) - cap))
    return ", ".join(parts)

#: The two priority lanes. ``interactive`` requests default to the SLO
#: deadline; ``batch`` requests default to a 4x looser one and are the
#: first shed under overload — in exchange they ride along in whatever
#: bucket capacity the interactive lane leaves free, which is what
#: keeps them starvation-free AND keeps occupancy high.
LANES = ("interactive", "batch")


class RequestShed(MXNetError):
    """Typed overload-shed error: the scheduler dropped this request
    (lowest-priority, most-expired first) instead of convoying every
    queued request past the SLO. Safe to retry on another replica —
    the fleet router maps it onto its retryable taxonomy."""


def bucket_ladder(max_batch: int, dp: int = 1,
                  spec: Optional[str] = None,
                  mesh=None) -> Tuple[int, ...]:
    """The padded batch-size ladder: every dispatched batch rounds up
    to the next rung, so the serving path compiles at most
    ``len(ladder)`` executables total. Default rungs are powers of two
    from ``dp`` up to ``max_batch``; an explicit ``spec`` (or
    ``MXNET_TPU_SERVE_BUCKETS``) is a comma list. Every rung is rounded
    up to a multiple of the mesh's BATCH-SHARDING EXTENT so the batch
    axis always shards evenly: pass ``mesh`` and the extent is the
    product of its data axes (``dp``, ``dp x fsdp`` — and on a
    ``(dp, tp)`` serving mesh just ``dp``: rounding to ``mesh.size``
    there would over-pad every bucket by the tp factor), or pass the
    extent directly as ``dp``."""
    if mesh is not None:
        from .parallel.sharding import batch_shard_extent

        dp = batch_shard_extent(mesh)
    dp = max(1, int(dp))
    if spec is None:
        spec = _env.get("MXNET_TPU_SERVE_BUCKETS")
    if spec:
        rungs = [int(s) for s in str(spec).split(",") if s.strip()]
    else:
        rungs, b = [], 1
        while b < max_batch:
            rungs.append(b)
            b *= 2
        rungs.append(max_batch)
    ladder = sorted({max(dp, -(-r // dp) * dp) for r in rungs})
    if any(r <= 0 for r in ladder) or not ladder:
        raise MXNetError("invalid bucket ladder %r" % (ladder,))
    if ladder[-1] < max_batch:
        ladder.append(-(-max_batch // dp) * dp)
    return tuple(ladder)


# ---------------------------------------------------------------------------
# the adaptive control plane: arrival rate, service time, wait window
# ---------------------------------------------------------------------------

class ArrivalRateEstimator:
    """EWMA of the request arrival rate (req/s), fed one ``observe()``
    per accepted request. ``rate()`` decays toward zero while no
    requests arrive (bounded above by ``1/idle``), so a burst followed
    by silence does not keep the scheduler waiting for phantom
    arrivals. ``clock`` is injectable for fake-clock tests."""

    def __init__(self, clock=time.perf_counter, alpha: float = 0.2):
        self._clock = clock
        self._alpha = float(alpha)
        self._lock = threading.Lock()
        self._last: Optional[float] = None
        self._rate = 0.0

    def observe(self):
        now = self._clock()
        with self._lock:
            if self._last is not None:
                dt = max(now - self._last, 1e-6)
                self._rate += self._alpha * (1.0 / dt - self._rate)
            self._last = now

    def rate(self) -> float:
        with self._lock:
            if self._last is None:
                return 0.0
            idle = self._clock() - self._last
            if idle <= 1e-6:
                return self._rate
            return min(self._rate, 1.0 / idle)


class ServiceTimeEstimator:
    """EWMA of the per-batch service wall time (stage + dispatch +
    d2h) keyed by bucket rung — the scheduler subtracts this from a
    request's deadline to know how long it can keep coalescing before
    the request can no longer be served in time. Unseen rungs borrow
    the worst known estimate (conservative), or ``default_ms`` before
    any dispatch has completed."""

    def __init__(self, default_ms: float = 2.0, alpha: float = 0.25):
        self._default = float(default_ms)
        self._alpha = float(alpha)
        self._est: dict = {}

    def observe(self, bucket: int, ms: float):
        cur = self._est.get(bucket)
        self._est[bucket] = (float(ms) if cur is None
                             else cur + self._alpha * (float(ms) - cur))

    def estimate_ms(self, bucket: int) -> float:
        est = self._est.get(bucket)
        if est is not None:
            return est
        return max(self._est.values()) if self._est else self._default


class AdaptiveWaitController:
    """Closed-loop coalescing window: widen the wait while the SLO
    probe shows p99 headroom (bigger buckets, better occupancy),
    collapse it toward the floor as the probe nears breach. The law is
    deliberately monotone: for the same state, a worse p99 never
    produces a longer wait — pinned by test.

    The ceiling defaults to half the SLO (capped at 50 ms) so the
    window alone can never spend the whole latency budget; the
    deadline-slack check in the scheduler bounds the rest.
    """

    def __init__(self, slo_ms: float, start_ms: float,
                 floor_ms: float = 0.2, ceil_ms: Optional[float] = None,
                 widen: float = 1.5, collapse: float = 0.5,
                 lo: float = 0.15, hi: float = 0.35):
        self.slo_ms = float(slo_ms or 0.0)
        if ceil_ms is None:
            ceil_ms = (min(50.0, 0.5 * self.slo_ms) if self.slo_ms
                       else float(start_ms))
        self.floor_ms = float(floor_ms)
        self.ceil_ms = max(self.floor_ms, float(ceil_ms))
        self.widen = float(widen)
        self.collapse = float(collapse)
        self.lo = float(lo)
        self.hi = float(hi)
        self.wait_ms = min(max(float(start_ms), self.floor_ms),
                           self.ceil_ms)
        self.updates = 0

    def update(self, p99_ms: Optional[float]) -> float:
        """One control step: feed the sliding-window p99, get the new
        wait. ``p99_ms=None`` (no samples yet) reads as full headroom."""
        self.updates += 1
        if not self.slo_ms:
            return self.wait_ms
        headroom = (1.0 if p99_ms is None
                    else 1.0 - float(p99_ms) / self.slo_ms)
        w = self.wait_ms
        if headroom < self.lo:
            w *= self.collapse
        elif headroom > self.hi:
            w *= self.widen
        self.wait_ms = min(self.ceil_ms, max(self.floor_ms, w))
        return self.wait_ms


class Request:
    """One in-flight inference request: the payload arrays (one per
    data name, leading axis = rows, normally 1) plus the completion
    event the scheduler signals once results (or an error) land.

    Every request carries a stable ``request_id`` (caller-provided or
    a fresh uuid): a hedged or retried duplicate re-submitted with the
    same id is deduped at the scheduler instead of dispatched twice —
    safe because the ``FusedInfer`` dispatch is idempotent (nothing
    donated, no state mutated). ``deadline_ms``/``priority`` form the
    scheduling envelope: the deadline drives earliest-deadline-first
    dispatch and overload shedding; the lane picks the default
    deadline and the shed order."""

    __slots__ = ("arrays", "rows", "t_enq", "_done", "result", "error",
                 "queue_ms", "latency_ms", "request_id", "deadline_ms",
                 "priority", "t_deadline", "t_adm", "sched_idle_ms",
                 "components", "trace_ctx")

    def __init__(self, arrays: Sequence[np.ndarray],
                 request_id: Optional[str] = None,
                 deadline_ms: Optional[float] = None,
                 priority: Optional[str] = None,
                 trace_ctx: Optional[dict] = None):
        self.arrays = [np.asarray(a) for a in arrays]
        self.rows = int(self.arrays[0].shape[0])
        self.t_enq = time.perf_counter()
        self._done = threading.Event()
        self.result: Optional[List[np.ndarray]] = None
        self.error: Optional[BaseException] = None
        self.queue_ms = 0.0
        self.latency_ms = 0.0
        self.sched_idle_ms = 0.0
        self.request_id = request_id or uuid.uuid4().hex
        self.deadline_ms = (None if not deadline_ms
                            else float(deadline_ms))
        self.priority = priority or "interactive"
        self.t_deadline: Optional[float] = None   # stamped at submit
        self.t_adm = self.t_enq
        self.components: Optional[dict] = None
        # the distributed-trace context this request rode in with
        # (None = untraced); the scheduler parents its decomposition
        # spans under it
        self.trace_ctx = trace_ctx

    def get(self, timeout: Optional[float] = None) -> List[np.ndarray]:
        """Block until the scheduler served this request; returns the
        per-row result arrays (post-processing outputs when the server
        was built with ``top_k``, else the raw forward outputs)."""
        if not self._done.wait(timeout):
            raise MXNetError("inference request timed out after %ss"
                             % timeout)
        if self.error is not None:
            raise self.error
        return self.result

    def done(self) -> bool:
        return self._done.is_set()


class BatchScheduler:
    """Deadline-aware continuous batcher in front of a compiled-once
    infer callable.

    ``infer_fn(placed_arrays) -> (outs, post)`` is dispatched once per
    coalesced batch (a :class:`~mxnet_tpu.fused_step.FusedInfer`); the
    scheduler owns request admission, the priority lanes, the bucket
    ladder, padding (via
    :class:`~mxnet_tpu.io_pipeline.RequestStager`), per-request result
    slicing, the latency decomposition and the SLO window. One daemon
    worker thread ("mxtpu-serve-batcher") runs the loop; ``close()``
    joins it after draining the queue.

    The dispatch decision (``_decide``) fires on the first of:

    * **full** — pending rows reached ``max_batch``;
    * **deadline** — the earliest pending deadline minus the rolling
      service-time estimate (x2 safety) is about to run out;
    * **rung_fill** — pending rows sit exactly on a bucket rung and
      the arrival-rate estimate says the next rung is out of reach;
    * **idle** — (adaptive) the arrival rate says nothing more is
      plausibly arriving inside the window, so holding a nearly-empty
      bucket open buys nothing;
    * **window** — the coalescing window (adaptive or static
      ``max_wait_ms``) expired. When crossing the next bucket rung is
      reachable within both the remaining deadline slack and twice the
      window, the window stretches to meet the fill.

    ``clock`` and ``autostart=False`` make the whole decision plane
    drivable from a fake-clock test via :meth:`step`.
    """

    #: deadline-slack safety: dispatch when ``deadline - now`` falls
    #: below ``SVC_SAFETY * service_estimate + SLACK_MARGIN_MS``
    SVC_SAFETY = 2.0
    SLACK_MARGIN_MS = 2.0
    #: the window may stretch to this multiple of itself to finish
    #: filling a bucket rung that is reachable within the slack
    FILL_STRETCH = 2.0

    def __init__(self, infer_fn, data_shapes: Sequence[tuple],
                 max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 buckets: Optional[Sequence[int]] = None,
                 slo_ms: Optional[float] = None,
                 dp: int = 1, place=None, slo_window: int = 512,
                 adaptive: Optional[bool] = None,
                 default_deadline_ms: Optional[float] = None,
                 batch_deadline_ms: Optional[float] = None,
                 clock=time.perf_counter, autostart: bool = True):
        self._infer = infer_fn
        self._data_shapes = [tuple(s) for s in data_shapes]
        dp = max(1, int(dp))
        if max_batch is None:
            max_batch = _env.get("MXNET_TPU_SERVE_MAX_BATCH")
        max_batch = max(dp, -(-int(max_batch) // dp) * dp)
        self.max_batch = max_batch
        self.max_wait_ms = float(
            _env.get("MXNET_TPU_SERVE_MAX_WAIT_MS")
            if max_wait_ms is None else max_wait_ms)
        if buckets is None:
            self.buckets = bucket_ladder(max_batch, dp=dp)
        else:
            self.buckets = bucket_ladder(max_batch, dp=dp,
                                         spec=",".join(map(str, buckets)))
        self._rung_set = frozenset(self.buckets)
        self.slo_ms = float(_env.get("MXNET_TPU_SERVE_SLO_MS")
                            if slo_ms is None else slo_ms)
        self._clock = clock
        # adaptive control plane: needs an SLO to close the loop on
        if adaptive is None:
            adaptive = _env.get("MXNET_TPU_SERVE_ADAPTIVE")
        self.adaptive = bool(adaptive) and self.slo_ms > 0
        self._arrival = ArrivalRateEstimator(clock=clock)
        self._svc = ServiceTimeEstimator()
        self._ctl = AdaptiveWaitController(self.slo_ms, self.max_wait_ms)
        # lane deadline defaults: explicit arg > env knob > SLO (and 4x
        # the interactive default for the batch lane)
        dflt = float(_env.get("MXNET_TPU_SERVE_DEADLINE_MS")
                     if default_deadline_ms is None
                     else default_deadline_ms)
        if dflt <= 0:
            dflt = self.slo_ms if self.adaptive else 0.0
        bdflt = float(_env.get("MXNET_TPU_SERVE_BATCH_DEADLINE_MS")
                      if batch_deadline_ms is None else batch_deadline_ms)
        if bdflt <= 0:
            bdflt = 4.0 * dflt if dflt else 0.0
        self._deadline_default_ms = {"interactive": dflt, "batch": bdflt}
        self._shed_rows = 2 * self.max_batch
        self._stager = RequestStager(place=place)
        self._q: _queue.Queue = _queue.Queue()
        self._pending: List[Request] = []
        self._pending_rows = 0
        self._dispatch_reason = ""
        self._stop = threading.Event()
        self._closed = False
        self._started = False
        self._lock = threading.Lock()
        self._lat: List[float] = []
        self._lat_cap = int(slo_window)
        # controller feedback window: (t_done, latency_ms), time-bounded
        # in recent_quantile so a transient ages out by wall clock, not
        # by waiting for enough new samples to push it off the end
        self._recent: collections.deque = collections.deque(maxlen=64)
        self._warmed: set = set()
        self._served = 0
        self._batches = 0
        self._occ_sum = 0.0
        self._in_flight = 0
        self._slo_breaches = 0
        # per-scheduler latency histogram: a standalone (non-registry)
        # instance so two in-process replicas never share one series —
        # this is the payload an obswatch InProc scrape federates
        self._lat_hist = _tel.Histogram("serve.request_ms")
        self._lane = {lane: {"served": 0, "shed": 0} for lane in LANES}
        self._depth_samples: collections.deque = collections.deque(
            maxlen=4096)
        self._traj: collections.deque = collections.deque(maxlen=512)
        self._t0 = self._clock()
        # retry-safety: request-id -> Request. In-flight dedup is always
        # safe (same object); completed-result reuse additionally needs
        # the infer fn tagged idempotent (FusedInfer is: nothing
        # donated, no state mutated).
        self._idempotent = bool(getattr(infer_fn, "idempotent", False))
        self._inflight_ids: dict = {}
        self._done_ids: collections.OrderedDict = collections.OrderedDict()
        self._done_cap = 1024
        # the last SLO-breaching traced request: the slo_probe attaches
        # it so a degraded /healthz names a concrete reproducible trace
        self._last_breach_trace: Optional[str] = None
        self._worker: Optional[threading.Thread] = None
        if autostart:
            self.start()

    def start(self):
        """Start the worker loop (called by ``__init__`` unless
        ``autostart=False``). A second call is a programming error —
        the double-start guard keeps two batcher threads from racing
        on one queue."""
        with self._lock:
            if self._closed:
                raise MXNetError("BatchScheduler is closed; build a "
                                 "new one instead of restarting it")
            if self._started:
                raise MXNetError("BatchScheduler already started "
                                 "(double start)")
            self._started = True
        self._worker = threading.Thread(target=self._run,
                                        name="mxtpu-serve-batcher",
                                        daemon=True)
        self._worker.start()

    def rebind_infer(self, infer_fn, place=None):
        """Atomically re-point dispatching at a new infer callable (and
        the stager at its placement fn): the server rebuilt its
        FusedInfer after a re-bind across mesh factorings. Taken under
        the scheduler lock so a concurrently-running ``_dispatch``
        finishes whole on whichever executable it already read."""
        with self._lock:
            self._infer = infer_fn
            if place is not None:
                self._stager.rebind_place(place)

    # -- intake ------------------------------------------------------------
    def submit(self, arrays: Sequence[np.ndarray],
               request_id: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               priority: Optional[str] = None,
               trace_ctx: Optional[dict] = None) -> Request:
        """Enqueue one request (arrays follow the server's data names;
        leading axis = rows). Returns immediately; block on
        ``Request.get()``. ``deadline_ms`` is the remaining latency
        budget (defaults to the lane's configured deadline, then the
        SLO); ``priority`` picks the lane (``interactive`` default);
        ``trace_ctx`` is the distributed-trace context propagated from
        the fleet router (the dispatch decomposition lands under it as
        child spans). Re-submitting a ``request_id`` that is already
        in flight (or recently served, when the infer fn is
        idempotent) returns the original request instead of
        dispatching the work twice and counts
        ``serve.duplicate_requests``."""
        priority = priority or "interactive"
        if priority not in LANES:
            raise MXNetError("unknown priority lane %r (expected one "
                             "of %s)" % (priority, ", ".join(LANES)))
        if deadline_ms is None:
            deadline_ms = self._deadline_default_ms[priority] or None
        req = Request(arrays, request_id, deadline_ms=deadline_ms,
                      priority=priority, trace_ctx=trace_ctx)
        req.t_enq = self._clock()
        req.t_adm = req.t_enq
        if req.deadline_ms:
            req.t_deadline = req.t_enq + req.deadline_ms / 1e3
        if len(req.arrays) != len(self._data_shapes):
            raise MXNetError("expected %d input arrays, got %d"
                             % (len(self._data_shapes), len(req.arrays)))
        for a, shape in zip(req.arrays, self._data_shapes):
            if tuple(a.shape[1:]) != tuple(shape[1:]):
                raise MXNetError(
                    "request row shape %r does not match the served "
                    "model's %r (batch ladder only pads the batch "
                    "axis; other dims would retrace)"
                    % (tuple(a.shape[1:]), tuple(shape[1:])))
        if req.rows > self.max_batch:
            raise MXNetError("request of %d rows exceeds max_batch=%d"
                             % (req.rows, self.max_batch))
        if self._closed:
            raise MXNetError("BatchScheduler is closed")
        with self._lock:
            dup = self._inflight_ids.get(req.request_id)
            if dup is None and self._idempotent:
                dup = self._done_ids.get(req.request_id)
            if dup is not None:
                _tel.inc("serve.duplicate_requests")
                return dup
            self._inflight_ids[req.request_id] = req
            self._in_flight += 1
        self._arrival.observe()
        _tel.inc("serve.requests")
        _tel.set_gauge("serve.in_flight", self.in_flight())
        self._q.put(req)
        return req

    def in_flight(self) -> int:
        """Requests accepted but not yet completed (the /healthz
        identity payload reads this)."""
        with self._lock:
            return self._in_flight

    def _finish(self, req: Request, served: bool):
        """Completion bookkeeping: retire the request id (into the
        dedup cache when served and the infer fn is idempotent) and
        drop it from the in-flight count."""
        with self._lock:
            if self._inflight_ids.pop(req.request_id, None) is not None:
                self._in_flight -= 1
            if served and self._idempotent:
                self._done_ids[req.request_id] = req
                while len(self._done_ids) > self._done_cap:
                    self._done_ids.popitem(last=False)

    def infer(self, arrays: Sequence[np.ndarray],
              timeout: Optional[float] = 60.0,
              deadline_ms: Optional[float] = None,
              priority: Optional[str] = None) -> List[np.ndarray]:
        """Synchronous convenience: submit + wait."""
        return self.submit(arrays, deadline_ms=deadline_ms,
                           priority=priority).get(timeout)

    # -- scheduling loop ---------------------------------------------------
    def _admit_intake(self, block_s: float = 0.0):
        """Move queued requests into the pending set, blocking at most
        ``block_s`` for the first one."""
        try:
            if block_s > 0:
                self._admit(self._q.get(timeout=block_s))
            while True:
                self._admit(self._q.get_nowait())
        except _queue.Empty:
            pass

    def _admit(self, req: Request):
        now = self._clock()
        req.t_adm = now
        req.queue_ms = (now - req.t_enq) * 1e3
        self._pending.append(req)
        self._pending_rows += req.rows
        depth = self._pending_rows + self._q.qsize()
        self._depth_samples.append(depth)
        _tel.set_gauge("serve.queue_depth", depth)

    def _bucket_for(self, rows: int) -> int:
        return next(b for b in self.buckets if b >= min(rows,
                                                        self.buckets[-1]))

    def _maybe_shed(self, now: float):
        """Overload shedding: when the backlog exceeds twice
        ``max_batch`` rows, convoying everyone past the SLO serves
        nobody — fail the lowest-priority, most-expired requests with
        :class:`RequestShed` until one dispatch can clear the rest.
        Never sheds while draining on close (those are served)."""
        if self._stop.is_set() or self._pending_rows <= self._shed_rows:
            return
        victims = [r for r in self._pending
                   if r.t_deadline is not None and now > r.t_deadline]
        if not victims:
            return
        victims.sort(key=lambda r: (0 if r.priority == "batch" else 1,
                                    r.t_deadline))
        shed, rows = [], self._pending_rows
        for r in victims:
            if rows <= self.max_batch:
                break
            shed.append(r)
            rows -= r.rows
        if not shed:
            return
        shed_ids = {id(r) for r in shed}
        self._pending = [r for r in self._pending
                         if id(r) not in shed_ids]
        self._pending_rows = rows
        trc = _dtrace._TRACER   # disabled cost: this one None check
        for r in shed:
            _tel.inc("serve.shed_requests")
            _tel.inc("serve.shed.%s" % r.priority)
            with self._lock:
                self._lane[r.priority]["shed"] += 1
            if trc is not None and r.trace_ctx is not None:
                trc.emit("serve.shed", r.trace_ctx, r.t_enq, now,
                         tags={"shed": True, "priority": r.priority,
                               "request_id": r.request_id})
            r.error = RequestShed(
                "request %s (%s lane) shed under overload: deadline "
                "%.1fms expired %.1fms ago with %d rows queued"
                % (r.request_id, r.priority, r.deadline_ms or 0.0,
                   (now - r.t_deadline) * 1e3, self._pending_rows))
            self._finish(r, served=False)
            r._done.set()
        _log.warning("shed %d request(s) under overload: %s",
                     len(shed), _corr_ids(shed))

    def _decide(self, now: float) -> Optional[float]:
        """The dispatch decision over the pending set: ``None`` means
        dispatch now (``_dispatch_reason`` says why), a positive float
        is how long coalescing may continue before re-evaluating."""
        rows = self._pending_rows
        if rows >= self.max_batch:
            self._dispatch_reason = "full"
            return None
        hold0 = min(r.t_adm for r in self._pending)
        window_ms = self._ctl.wait_ms if self.adaptive else self.max_wait_ms
        window_s = window_ms / 1e3
        window_end = hold0 + window_s
        bucket = self._bucket_for(rows)
        est_s = (self._svc.estimate_ms(bucket) * self.SVC_SAFETY
                 + self.SLACK_MARGIN_MS) / 1e3
        slack_end = None
        for r in self._pending:
            if r.t_deadline is not None:
                e = r.t_deadline - est_s
                if slack_end is None or e < slack_end:
                    slack_end = e
        if slack_end is not None and now >= slack_end:
            # the earliest deadline is about to run out of slack:
            # dispatch immediately, whatever the fill looks like
            self._dispatch_reason = "deadline"
            return None
        end = window_end if slack_end is None else min(window_end,
                                                       slack_end)
        if self.adaptive:
            rate = self._arrival.rate()
            nxt = next((b for b in self.buckets if b > rows), None)
            fill_s = ((nxt - rows) / rate
                      if nxt is not None and rate > 0 else None)
            if fill_s is not None:
                # coalescing would cross the next bucket rung within
                # the remaining slack (and a bounded stretch of the
                # window, never past the controller's ceiling — the
                # total hold must stay within the wait the control
                # loop is accountable for): wait for the fill
                ext_end = hold0 + min(self.FILL_STRETCH * window_s,
                                      self._ctl.ceil_ms / 1e3)
                if slack_end is not None:
                    ext_end = min(ext_end, slack_end)
                if now + fill_s <= ext_end:
                    end = max(end, now + fill_s)
            if rows in self._rung_set and (fill_s is None
                                           or now + fill_s > end):
                # sitting exactly on a rung with the next one out of
                # reach: ship a perfectly full bucket now
                self._dispatch_reason = "rung_fill"
                return None
            if rate * max(end - now, 0.0) < 1.0:
                # light load: nothing else is plausibly arriving inside
                # the window — dispatch now instead of holding a
                # nearly-empty bucket open for nobody
                self._dispatch_reason = "idle"
                return None
        if now >= end:
            self._dispatch_reason = "window"
            return None
        return end - now

    def _pack(self, now: float) -> List[Request]:
        """Earliest-deadline-first packing: take pending requests in
        EDF order (no deadline sorts last, FIFO within ties) up to
        ``max_batch`` rows, never splitting a request. Whatever the
        urgent lane leaves free is filled by the batch lane — that
        ride-along is both the occupancy win and the
        starvation-freedom guarantee."""
        self._pending.sort(key=lambda r: (
            r.t_deadline if r.t_deadline is not None else float("inf"),
            r.t_adm))
        batch: List[Request] = []
        rest: List[Request] = []
        rows = 0
        for r in self._pending:
            if rows + r.rows <= self.max_batch:
                batch.append(r)
                rows += r.rows
            else:
                rest.append(r)
        self._pending = rest
        self._pending_rows = sum(r.rows for r in rest)
        return batch

    def step(self) -> Optional[str]:
        """One manual scheduling step (fake-clock tests drive this
        with ``autostart=False``): admit intake, shed under overload,
        evaluate the dispatch decision, dispatch at most one batch.
        Returns the dispatch reason, ``"shed"`` when shedding emptied
        the pending set, ``"wait"`` while coalescing continues, or
        ``None`` when idle."""
        self._admit_intake(0.0)
        if not self._pending:
            return None
        now = self._clock()
        self._maybe_shed(now)
        if not self._pending:
            return "shed"
        if self._decide(now) is not None:
            return "wait"
        reason = self._dispatch_reason
        self._dispatch(self._pack(now))
        return reason

    def _run(self):
        while True:
            if self._stop.is_set():
                self._admit_intake(0.0)
                if not self._pending:
                    break
                batch = self._pack(self._clock())
            else:
                self._admit_intake(0.0 if self._pending else 0.05)
                if not self._pending:
                    continue
                now = self._clock()
                self._maybe_shed(now)
                if not self._pending:
                    continue
                wait_s = self._decide(now)
                if wait_s is not None:
                    # sleep on the intake queue so a new arrival
                    # re-evaluates the decision immediately
                    self._admit_intake(min(wait_s, 0.05))
                    continue
                batch = self._pack(now)
            if not batch:
                continue
            try:
                self._dispatch(batch)
            except BaseException as e:   # noqa: BLE001 (fail the batch,
                _tel.inc("serve.errors")  # not the serving loop)
                for req in batch:
                    req.error = e
                    self._finish(req, served=False)
                    req._done.set()
                _log.exception("serve batch failed (%d requests: %s)",
                               len(batch), _corr_ids(batch))

    def _dispatch(self, batch: List[Request]):
        import jax

        if _faults.fires("drop_response"):
            # the response is lost on the wire: the work is abandoned,
            # callers see a timeout, and the router's deadline-budgeted
            # retry path has to recover the request elsewhere
            _tel.inc("serve.dropped_responses")
            _log.warning("response dropped (injected fault) for %d "
                         "request(s): %s", len(batch),
                         _corr_ids(batch))
            for req in batch:
                self._finish(req, served=False)
            return
        if _faults.fires("slow_replica"):
            time.sleep(_faults.slow_ms() / 1e3)

        t0 = self._clock()
        rows = sum(r.rows for r in batch)
        bucket = next(b for b in self.buckets if b >= rows)
        for req in batch:
            req.sched_idle_ms = (t0 - req.t_adm) * 1e3
        placed, pad = self._stager.stage([r.arrays for r in batch],
                                         bucket)
        t1 = self._clock()
        outs, post = self._infer(placed)
        results = list(post) if post else list(outs)
        jax.block_until_ready(results)   # graft: host-sync
        t2 = self._clock()
        host = [np.asarray(a) for a in results]   # graft: host-sync
        t3 = self._clock()

        h2d_ms = (t1 - t0) * 1e3
        dispatch_ms = (t2 - t1) * 1e3
        d2h_ms = (t3 - t2) * 1e3
        occupancy = rows / float(bucket)
        self._svc.observe(bucket, (t3 - t0) * 1e3)
        _tel.observe("serve.batch_occupancy", occupancy)
        _tel.inc("serve.batches")

        worst_trace = None
        trc = _dtrace._TRACER   # disabled cost: this one None check
        if trc is not None:
            worst_trace = self._emit_spans(trc, batch, t0, t1, t2, t3,
                                           rows, bucket, occupancy)

        off, worst = 0, 0.0
        for req in batch:
            req.result = [h[off:off + req.rows] for h in host]
            off += req.rows
            req.latency_ms = (t3 - req.t_enq) * 1e3
            # the exact per-request wall-time decomposition: the five
            # components sum to latency_ms by construction (pinned by
            # test); pad_waste stays an overlay, outside the sum
            req.components = {
                "queue_ms": req.queue_ms,
                "sched_idle_ms": req.sched_idle_ms,
                "h2d_ms": h2d_ms, "dispatch_ms": dispatch_ms,
                "d2h_ms": d2h_ms}
            for name, v in req.components.items():
                _tel.observe("serve." + name, v)
            _tel.observe("serve.pad_waste_ms",
                         dispatch_ms * (1 - occupancy))
            _tel.observe("serve.request_ms", req.latency_ms)
            worst = max(worst, req.latency_ms)
            with self._lock:
                self._lane[req.priority]["served"] += 1
            self._finish(req, served=True)
            req._done.set()
        _tel.set_gauge("serve.in_flight", self.in_flight())
        with self._lock:
            self._served += rows
            self._batches += 1
            self._occ_sum += occupancy
            if self.slo_ms:
                self._slo_breaches += sum(
                    1 for r in batch if r.latency_ms > self.slo_ms)
            for r in batch:
                self._lat_hist.observe(r.latency_ms)
            self._lat.extend(r.latency_ms for r in batch)
            if len(self._lat) > self._lat_cap:
                del self._lat[:len(self._lat) - self._lat_cap]
            # a bucket's first dispatch carries its one-time compile:
            # real latency for the SLO probe above, but poison as
            # controller feedback (one 300 ms trace would pin the p99
            # and collapse the wait long after steady state resumed)
            if bucket in self._warmed:
                self._recent.extend((t3, r.latency_ms) for r in batch)
            else:
                self._warmed.add(bucket)
        # close the adaptive loop off the sliding-window p99, and leave
        # an observable trajectory behind
        depth = self._pending_rows + self._q.qsize()
        if self.adaptive:
            # control on the RECENT p99, not the full SLO window: the
            # probe's long memory is right for alerting but a controller
            # fed stale samples re-collapses on a transient long after
            # it healed
            self._ctl.update(self.recent_quantile(0.99))
        _tel.set_gauge("serve.adaptive_wait_ms", self._ctl.wait_ms)
        _tel.set_gauge("serve.arrival_rate", self._arrival.rate())
        _tel.set_gauge("serve.queue_depth", depth)
        self._traj.append({
            "t_s": round(t3 - self._t0, 4),
            "wait_ms": round(self._ctl.wait_ms
                             if self.adaptive else self.max_wait_ms, 3),
            "queue_depth": depth, "rows": rows, "bucket": bucket,
            "occupancy": round(occupancy, 4),
            "reason": self._dispatch_reason,
            "arrival_rps": round(self._arrival.rate(), 2)})
        # the serving step record: the SlowRequestDetector keys off
        # request_ms/slo_ms, and the /healthz anomaly count moves
        extra = {
            "request_ms": round(worst, 3),
            "slo_ms": self.slo_ms,
            "serve_rows": rows, "serve_bucket": bucket,
            "adaptive_wait_ms": round(self._ctl.wait_ms, 3),
            "queue_depth": depth}
        if worst_trace is not None:
            extra["worst_trace_id"] = worst_trace
        _tracing.record_step((t3 - t0) * 1e3, extra=extra)

    def _emit_spans(self, trc, batch, t0, t1, t2, t3, rows, bucket,
                    occupancy):
        """Traced requests' decomposition spans: under each request's
        propagated context, a ``serve.request`` span covering enqueue
        to completion with the five exact components as children
        (their durations sum to request_ms by construction), every
        dispatch span cross-linked (``batch=<id>``) to one shared
        ``serve.batch_dispatch`` span tagged with the bucket,
        occupancy and whether this dispatch carried the bucket's
        one-time compile (the xprof registry's count moves in step).
        Returns the worst traced request's trace id (or None)."""
        batch_sid = None
        worst_ms, worst_trace = -1.0, None
        # _warmed gains the bucket only after this dispatch; compiles
        # is the FusedInfer/xprof-registry counter when present
        compiled = bucket not in self._warmed
        for req in batch:
            ctx = req.trace_ctx
            if ctx is None:
                continue
            req_ms = (t3 - req.t_enq) * 1e3
            breach = bool(self.slo_ms) and req_ms > self.slo_ms
            sid = trc.emit(
                "serve.request", ctx, req.t_enq, t3,
                tags={"request_id": req.request_id,
                      "priority": req.priority, "rows": req.rows,
                      "slo_breach": breach})
            if batch_sid is None:
                # one shared batch-dispatch span (first traced
                # request's tree hosts it; the rest cross-link)
                batch_sid = trc.emit(
                    "serve.batch_dispatch", (ctx["t"], sid), t1, t2,
                    tags={"bucket": bucket, "rows": rows,
                          "occupancy": round(occupancy, 4),
                          "compile": compiled,
                          "compiles": getattr(self._infer, "compiles",
                                              None),
                          "requests": len(batch)})
            parent = (ctx["t"], sid)
            trc.emit("serve.queue", parent, req.t_enq, req.t_adm)
            trc.emit("serve.sched_idle", parent, req.t_adm, t0)
            trc.emit("serve.h2d", parent, t0, t1,
                     tags={"pad_rows": bucket - rows,
                           "fastpath": self._stager.last_fastpath,
                           "h2d_bytes": self._stager.last_bytes})
            trc.emit("serve.dispatch", parent, t1, t2,
                     tags={"batch": batch_sid, "bucket": bucket,
                           "occupancy": round(occupancy, 4),
                           "compile": compiled})
            trc.emit("serve.d2h", parent, t2, t3)
            if breach:
                self._last_breach_trace = ctx["t"]
            if req_ms > worst_ms:
                worst_ms, worst_trace = req_ms, ctx["t"]
        return worst_trace

    # -- SLO / stats -------------------------------------------------------
    def latency_quantile(self, q: float) -> Optional[float]:
        with self._lock:
            lat = sorted(self._lat)
        if not lat:
            return None
        return lat[min(len(lat) - 1, int(q * len(lat)))]

    def recent_quantile(self, q: float,
                        window_s: float = 0.5) -> Optional[float]:
        """Quantile over recently served requests — the adaptive
        controller's feedback signal (the full ``slo_window`` stays the
        alerting probe). Bounded both ways: at most the last 64 samples
        AND only those finished within ``window_s``, so a latency spike
        stops steering the controller once it is ``window_s`` old even
        if traffic is too slow to displace it. ``None`` (nothing recent)
        reads as full headroom."""
        cutoff = self._clock() - float(window_s)
        with self._lock:
            lat = sorted(ms for (t, ms) in self._recent if t >= cutoff)
        if not lat:
            return None
        return lat[min(len(lat) - 1, int(q * len(lat)))]

    def slo_probe(self) -> Optional[dict]:
        """Health probe for /healthz: failing detail once the sliding
        p99 exceeds the SLO, None while healthy (or SLO unset). The
        failing payload carries the controller state so the operator
        sees where the adaptive wait was when the tail broke."""
        if not self.slo_ms:
            return None
        p99 = self.latency_quantile(0.99)
        if p99 is not None and p99 > self.slo_ms:
            detail = {"p99_ms": round(p99, 3), "slo_ms": self.slo_ms}
            detail.update(self.controller_state())
            if self._last_breach_trace is not None:
                # a concrete reproducible trace for the degradation:
                # `trace_report --view waterfall <id>` renders it
                detail["worst_trace_id"] = self._last_breach_trace
            return detail
        return None

    def controller_state(self) -> dict:
        """The adaptive control plane, as one JSON-able dict (merged
        into /healthz and ``stats()``)."""
        return {"adaptive": self.adaptive,
                "adaptive_wait_ms": round(
                    self._ctl.wait_ms if self.adaptive
                    else self.max_wait_ms, 3),
                "arrival_rate_rps": round(self._arrival.rate(), 2),
                "queue_depth": self._pending_rows + self._q.qsize()}

    def occupancy_snapshot(self) -> dict:
        """Monotone counters for occupancy deltas (mean occupancy
        between two snapshots = ``Δocc_sum / Δbatches``)."""
        with self._lock:
            return {"batches": self._batches, "occ_sum": self._occ_sum,
                    "served": self._served}

    def metrics_payload(self) -> dict:
        """This scheduler's metrics as a flat ``name -> export`` dict —
        the /metrics-equivalent payload an InProc fleet scrape reads
        directly (no socket). Counters export ints, gauges floats, the
        latency histogram a bucketed summary dict carrying its exact
        sample ring so the federator's fleet percentiles stay exact at
        smoke scale. Names match the process-global telemetry series so
        a subprocess replica's real /metrics merges with these."""
        with self._lock:
            served = self._served
            batches = self._batches
            occ_sum = self._occ_sum
            breaches = self._slo_breaches
        return {
            "serve.requests_served": served,
            "serve.batches": batches,
            "serve.slo_breaches": breaches,
            "serve.occupancy_sum": float(occ_sum),
            "serve.in_flight": float(self.in_flight()),
            "serve.queue_depth": float(self._pending_rows +
                                       self._q.qsize()),
            "serve.request_ms": self._lat_hist.export(include_sample=True),
        }

    def wait_trajectory(self) -> List[dict]:
        """The adaptive-wait trajectory: one sample per dispatched
        batch (time, wait, queue depth, occupancy, reason)."""
        return list(self._traj)

    def lane_stats(self) -> dict:
        with self._lock:
            return {lane: dict(v) for lane, v in self._lane.items()}

    def stats(self) -> dict:
        with self._lock:
            batches = self._batches
            served = self._served
            occ = self._occ_sum / batches if batches else 0.0
            lanes = {lane: dict(v) for lane, v in self._lane.items()}
        out = {"requests_served": served, "batches": batches,
               "mean_occupancy": round(occ, 4), "lanes": lanes}
        out.update(self.controller_state())
        depth = list(self._depth_samples)
        if depth:
            depth.sort()
            out["queue_depth_p50"] = depth[len(depth) // 2]
            out["queue_depth_p99"] = depth[min(len(depth) - 1,
                                               int(0.99 * len(depth)))]
            out["queue_depth_max"] = depth[-1]
        for name, q in (("p50_ms", 0.50), ("p99_ms", 0.99),
                        ("p999_ms", 0.999)):
            v = self.latency_quantile(q)
            if v is not None:
                out[name] = round(v, 3)
        return out

    # -- shutdown ----------------------------------------------------------
    def close(self, timeout: float = 10.0):
        """Graceful shutdown: stop intake, drain every queued request
        (served, not dropped), join the worker. Idempotent and safe to
        race from several threads (the fleet's monitor, a drain, and a
        context-manager exit may all call it)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._stop.set()
        if self._worker is not None:
            self._worker.join(timeout)
            if self._worker.is_alive():
                _log.warning("serve batcher still alive after %.1fs "
                             "join; leaking the (daemon) thread",
                             timeout)
        # a dispatch error could strand late submissions; fail them
        # rather than hang their callers
        leftovers = list(self._pending)
        self._pending = []
        self._pending_rows = 0
        while True:
            try:
                leftovers.append(self._q.get_nowait())
            except _queue.Empty:
                break
        if leftovers:
            _log.warning("failing %d queued request(s) at close: %s",
                         len(leftovers), _corr_ids(leftovers))
        for req in leftovers:
            req.error = MXNetError("BatchScheduler closed before the "
                                   "request was served")
            self._finish(req, served=False)
            # per-request completion event, not the worker's stop
            # signal — waking the caller after the join is the point
            req._done.set()  # graft: lifecycle-ok

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class InferenceServer:
    """A bound Module served behind a continuous batcher.

    Builds the compiled-once :class:`~mxnet_tpu.fused_step.FusedInfer`
    from the module's executor (params packed + replicated across the
    ``dp`` mesh when the module was bound over multiple devices;
    request batches sharded along ``dp``), starts the metrics/health
    server per ``MXNET_TPU_SERVE_PORT``, and registers the SLO health
    probe. ``top_k=0`` returns raw forward outputs, ``top_k=1`` the
    on-device argmax, ``top_k>1`` top-k (values, indices) — all
    computed inside the same single dispatch.
    """

    def __init__(self, module, top_k: int = 0,
                 max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 buckets: Optional[Sequence[int]] = None,
                 slo_ms: Optional[float] = None,
                 port: Optional[object] = None,
                 adaptive: Optional[bool] = None,
                 default_deadline_ms: Optional[float] = None,
                 batch_deadline_ms: Optional[float] = None,
                 tp: Optional[int] = None):
        from .fused_step import make_fused_infer
        from .parallel.sharding import batch_shard_extent

        if not module.binded or not module.params_initialized:
            raise MXNetError("InferenceServer needs a bound, "
                             "param-initialized module")
        group = module._exec_group
        ex = group.executor
        mesh = getattr(group, "_mesh", None)
        if tp is None:
            tp = int(_env.get("MXNET_TPU_SERVE_TP") or 0)
        self.tp = tp = max(1, int(tp))
        if tp > 1:
            mesh = self._tp_mesh(group, mesh, tp)
        self._module = module
        self._mesh = mesh
        # rungs round to the BATCH-sharding extent, not the device
        # count: on a (dp, tp) mesh only dp splits rows
        dp = batch_shard_extent(mesh) if mesh is not None else 1
        self.dp = dp
        self._fused = make_fused_infer(ex, module._data_names,
                                       top_k=top_k, mesh=mesh)
        self._top_k = top_k
        self._data_shapes = [d.shape for d in group.data_shapes]
        self.scheduler = BatchScheduler(
            self._fused, self._data_shapes, max_batch=max_batch,
            max_wait_ms=max_wait_ms, buckets=buckets, slo_ms=slo_ms,
            dp=dp, place=self._fused.place_batch, adaptive=adaptive,
            default_deadline_ms=default_deadline_ms,
            batch_deadline_ms=batch_deadline_ms)
        self._metrics = None
        self._own_metrics = False
        if port is None:
            port = _env.get("MXNET_TPU_SERVE_PORT")
        if port != "" and port is not None:
            self._metrics = _tracing.MetricsServer(int(port))
            self._own_metrics = True
        elif _tracing.metrics_server() is not None:
            self._metrics = _tracing.metrics_server()
        self._probe_name = "serve_slo:%d" % id(self)
        _tracing.register_health_probe(self._probe_name,
                                       self.scheduler.slo_probe)
        # replica identity on /healthz: the router and a human curl
        # read the same in-flight/served signal (rank, pid, uptime are
        # already in the base payload)
        self._info_name = "serve:%d" % id(self)
        _tracing.register_health_info(self._info_name, self.health_info)
        self._closed = False
        self._close_lock = threading.Lock()
        _log.info("serving: buckets=%s max_wait_ms=%s adaptive=%s dp=%d "
                  "tp=%d slo_ms=%s%s",
                  self.scheduler.buckets, self.scheduler.max_wait_ms,
                  self.scheduler.adaptive, dp, tp,
                  self.scheduler.slo_ms or "off",
                  " metrics on :%d" % self._metrics.port
                  if self._metrics else "")

    @staticmethod
    def _tp_mesh(group, mesh, tp: int):
        """Factor the module's devices into the ``(dp, tp)`` serving
        mesh: the same devices the group bound, reshaped so ``tp`` of
        them split the model and the rest replicate/shard the batch.
        Refuses (naming the knob) when ``tp`` does not divide the
        device count — silently dropping devices would serve a
        different capacity than the operator asked for."""
        from .parallel.sharding import make_mesh

        devices = (list(mesh.devices.flat) if mesh is not None
                   else [c.jax_device() for c in group.contexts])
        n = len(devices)
        if n % tp != 0:
            raise MXNetError(
                "MXNET_TPU_SERVE_TP=%d does not divide the %d-device "
                "group; pick a tp that factors the device count"
                % (tp, n))
        return make_mesh({"dp": n // tp, "tp": tp}, devices=devices)

    # -- serving API -------------------------------------------------------
    @property
    def port(self) -> Optional[int]:
        return self._metrics.port if self._metrics is not None else None

    @property
    def buckets(self) -> Tuple[int, ...]:
        return self.scheduler.buckets

    @property
    def compiles(self) -> int:
        """Executables built so far (bounded by len(buckets))."""
        return self._fused.compiles

    @property
    def closed(self) -> bool:
        return self._closed

    def start(self):
        """The server starts serving at construction; an explicit
        second start is the double-start bug this guard exists for."""
        self.scheduler.start()

    def submit(self, arrays, request_id: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               priority: Optional[str] = None,
               trace_ctx: Optional[dict] = None) -> Request:
        return self.scheduler.submit(arrays, request_id=request_id,
                                     deadline_ms=deadline_ms,
                                     priority=priority,
                                     trace_ctx=trace_ctx)

    def infer(self, arrays, timeout: Optional[float] = 60.0,
              deadline_ms: Optional[float] = None,
              priority: Optional[str] = None):
        return self.scheduler.infer(arrays, timeout,
                                    deadline_ms=deadline_ms,
                                    priority=priority)

    def refresh_params(self, host_params=None, digests=None):
        """Repack after a weight update — full re-pack after
        ``module.set_params`` (no arguments), or the delta-aware
        checkpoint-streamed path when ``host_params`` (name -> host
        ndarray) and optionally ``digests`` (the snapshot manifest's
        per-param sha256) are given: only params whose digest differs
        from the resident pack transfer
        (:meth:`~mxnet_tpu.fused_step.FusedInfer.refresh_params`).

        Either way the serving executable is first re-validated
        against the module's CURRENT executor and mesh factoring — a
        re-bind across meshes rebuilds the FusedInfer (and re-points
        the scheduler + stager at it) instead of serving a stale
        executable compiled for the old placement.

        Under an injected ``torn_swap`` fault the repack becomes
        non-atomic (half the pack, a sleep, the rest), so a dispatch
        inside the window would mix param versions — the fleet's
        drain-then-swap rolling update must mask that window, and the
        chaos tests prove it does."""
        self._ensure_executable()
        kw = {}
        if host_params is not None:
            kw = {"host_params": host_params, "digests": digests}
        if _faults.fires("torn_swap"):
            self._fused.refresh_params(
                torn_ms=max(_faults.slow_ms(), 1.0), **kw)
        else:
            self._fused.refresh_params(**kw)

    def refresh_from_snapshot(self, payload: dict):
        """Delta-refresh from a :func:`mxnet_tpu.checkpoint.snapshot`
        payload (the serve-while-training rollout path: training saves,
        the fleet ships the directory, each drained replica streams the
        changed params only)."""
        self.refresh_params(host_params=payload.get("params") or {},
                            digests=payload.get("param_digests"))

    def _ensure_executable(self):
        """Rebuild the FusedInfer when the module was re-bound onto a
        different executor or mesh factoring since construction. The
        scheduler's infer fn and the stager's place fn are re-pointed
        atomically under the scheduler lock — in-flight dispatches
        finish on the old executable, every later batch rides the new
        one."""
        group = self._module._exec_group
        mesh = self._mesh
        if self.tp <= 1:
            mesh = getattr(group, "_mesh", None)
        elif self._fused.stale_for(group.executor, self._mesh):
            # re-bound under tp: refactor the new device set
            mesh = self._tp_mesh(group, getattr(group, "_mesh", None),
                                 self.tp)
        if not self._fused.stale_for(group.executor, mesh):
            return
        from .fused_step import make_fused_infer

        self._mesh = mesh
        self._fused = make_fused_infer(group.executor,
                                       self._module._data_names,
                                       top_k=self._top_k, mesh=mesh)
        self._data_shapes = [d.shape for d in group.data_shapes]
        self.scheduler.rebind_infer(self._fused,
                                    self._fused.place_batch)
        _tel.inc("serve.executable_rebuilds")

    def health_info(self) -> dict:
        """Identity payload merged into /healthz by the tracing tier —
        replica identity plus the adaptive controller state, so the
        router and a human curl see where the scheduler sits."""
        info = {"in_flight": self.scheduler.in_flight(),
                "requests_served": self.scheduler.occupancy_snapshot()
                                       .get("served", 0)}
        info.update(self.scheduler.controller_state())
        return info

    def metrics_payload(self) -> dict:
        """Scrape payload for fleet federation (obswatch): the
        scheduler's per-replica metric series plus compile count."""
        out = self.scheduler.metrics_payload()
        out["serve.compiles"] = self.compiles
        return out

    def stats(self) -> dict:
        out = self.scheduler.stats()
        out["compiles"] = self.compiles
        out["buckets"] = list(self.buckets)
        out["dp"] = self.dp
        out["tp"] = self.tp
        out["in_flight"] = self.scheduler.in_flight()
        return out

    # -- shutdown ----------------------------------------------------------
    def close(self):
        """Idempotent and race-safe: the first caller wins, everyone
        else returns immediately (the fleet may close a replica from
        its monitor thread while a drain path does the same)."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
        _tracing.unregister_health_probe(self._probe_name)
        _tracing.unregister_health_info(self._info_name)
        self.scheduler.close()
        if self._own_metrics and self._metrics is not None:
            self._metrics.stop()
        self._metrics = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
