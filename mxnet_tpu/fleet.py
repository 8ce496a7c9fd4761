"""Fault-tolerant serving fleet: a router over InferenceServer replicas.

The single-host serving tier (:mod:`mxnet_tpu.serving`) holds its p99
SLO only while its one replica is healthy — any crash, stall, or
param-swap hiccup is an outage. The reference framework's parameter-
server plane answered that with server replication; this module
rebuilds the idea for TPU serving, per the ROADMAP: a
:class:`FleetRouter` spreads open-loop load over N replicas (in-process
or subprocess-backed) and keeps requests succeeding while individual
replicas die, stall, or swap weights.

The router's request path layers four classic reliability mechanisms:

* **consistent-hash session affinity** — a session key maps onto a
  vnode hash ring, so repeat requests land on the same replica while
  membership changes only remap ``1/N`` of sessions;
* **deadline-budgeted retries** — every request has one total deadline
  (``MXNET_TPU_FLEET_DEADLINE_MS``); per-attempt timeouts, exponential
  backoff with full jitter, and hedge waits are all clamped to the
  remaining budget, so a caller never waits longer than it asked for;
* **tail-latency hedging** (optional) — an attempt still pending at the
  router's observed p95 sends a duplicate (same request-id: the replica
  tier dedupes, see ``serve.duplicate_requests``) to a second replica
  and takes whichever answers first, abandoning the loser;
* **per-replica circuit breaker** — consecutive failures trip a
  replica open (load sheds to healthy peers); after a cooldown one
  half-open probe decides whether it rejoins or re-opens.

Replica lifecycle: the monitor thread detects crashed replicas off
their health signal (the same ``/healthz`` identity the serving tier
exports) and respawns them; ``remove_replica`` drains before it stops;
``refresh_params`` performs a glitch-free rolling swap — drain one
replica, swap, rejoin — so an injected ``torn_swap`` window is never
observable; autoscaling (optional) grows the fleet while replicas
report a degraded SLO and shrinks it after a sustained healthy streak.

Every claim above is provable under :mod:`mxnet_tpu.faults` injection:
the chaos tests (``tests/test_fleet.py``) kill a replica mid-load and
pin zero client-visible errors and zero mixed-version responses.
Goodput and recovery time on the chip are not measured.

>>> rng = __import__("random").Random(0)
>>> d0 = backoff_delay_s(0, 0.01, rng)
>>> 0.005 <= d0 < 0.01
True
>>> b = CircuitBreaker(fail_threshold=2, cooldown_s=10.0, clock=lambda: 0.0)
>>> b.record_failure(); b.record_failure()
False
True
>>> b.state
'open'
"""
from __future__ import annotations

import bisect
import hashlib
import logging
import os
import threading
import time
import uuid
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from random import Random
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import dtrace as _dtrace
from . import env as _env
from . import faults as _faults
from . import telemetry as _tel
from . import tracing as _tracing
from .base import MXNetError

__all__ = ["FleetError", "ReplicaCrash", "ReplicaError", "AttemptTimeout",
           "DeadlineExceeded", "NoReplicaAvailable", "CircuitBreaker",
           "backoff_delay_s", "Replica", "InProcReplica",
           "SubprocessReplica", "SocketReplica", "FleetRouter",
           "in_process", "in_subprocess", "in_socket"]

_log = logging.getLogger(__name__)


class FleetError(MXNetError):
    """Base class for fleet routing failures."""


class ReplicaCrash(FleetError):
    """The replica died (process gone, pipe broken, server closed)."""


class ReplicaError(FleetError):
    """The replica answered with an error (retryable elsewhere)."""


class AttemptTimeout(FleetError):
    """One attempt's per-replica timeout expired."""


class DeadlineExceeded(FleetError):
    """The request's total deadline budget ran out across attempts."""


class NoReplicaAvailable(FleetError):
    """No routable replica right now (all dead/draining/breaker-open)."""


# ---------------------------------------------------------------------------
# retry math
# ---------------------------------------------------------------------------

def backoff_delay_s(attempt: int, base_s: float, rng: Random,
                    cap_s: float = 1.0) -> float:
    """Exponential backoff with jitter for retry ``attempt`` (0-based):
    uniform in ``[e/2, e)`` where ``e = min(cap, base * 2^attempt)``.
    The half-open jitter interval keeps synchronized retry storms from
    re-colliding while never collapsing to a zero sleep."""
    e = min(float(cap_s), float(base_s) * (2.0 ** int(attempt)))
    return e * (0.5 + 0.5 * rng.random())


class CircuitBreaker:
    """Per-replica closed/open/half-open circuit breaker.

    ``fail_threshold`` consecutive failures trip it open; after
    ``cooldown_s`` one half-open probe request is let through — its
    success closes the breaker, its failure re-opens it for another
    cooldown. ``clock`` is injectable so the state machine is testable
    without sleeping. ``record_failure`` returns True exactly when this
    call tripped the breaker open (the router logs/counts trips off
    that edge)."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, fail_threshold: Optional[int] = None,
                 cooldown_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.fail_threshold = int(
            _env.get("MXNET_TPU_FLEET_BREAKER_FAILS")
            if fail_threshold is None else fail_threshold)
        self.cooldown_s = float(
            _env.get("MXNET_TPU_FLEET_BREAKER_COOLDOWN_MS") / 1e3
            if cooldown_s is None else cooldown_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._state = self.CLOSED
        self._fails = 0
        self._opened_at = 0.0
        self._probing = False
        self.trips = 0

    @property
    def state(self) -> str:
        with self._lock:
            if (self._state == self.OPEN
                    and self._clock() - self._opened_at >= self.cooldown_s):
                return self.HALF_OPEN
            return self._state

    def allow(self) -> bool:
        """May a request be routed here right now? In half-open state
        only one probe at a time is admitted."""
        with self._lock:
            if self._state == self.CLOSED:
                return True
            if self._state == self.OPEN:
                if self._clock() - self._opened_at < self.cooldown_s:
                    return False
                self._state = self.HALF_OPEN
                self._probing = True
                return True
            if self._probing:
                return False
            self._probing = True
            return True

    def record_success(self):
        with self._lock:
            self._state = self.CLOSED
            self._fails = 0
            self._probing = False

    def record_failure(self) -> bool:
        with self._lock:
            self._fails += 1
            if self._state == self.HALF_OPEN:
                self._state = self.OPEN
                self._opened_at = self._clock()
                self._probing = False
                self.trips += 1
                return True
            if (self._state == self.CLOSED
                    and self._fails >= self.fail_threshold):
                self._state = self.OPEN
                self._opened_at = self._clock()
                self.trips += 1
                return True
            return False


# ---------------------------------------------------------------------------
# replica handles
# ---------------------------------------------------------------------------

class Replica:
    """What the router drives. ``submit`` returns a waiter whose
    ``wait(timeout_s)`` yields the per-request result arrays or raises
    (:class:`AttemptTimeout` on timeout, :class:`ReplicaCrash` when the
    replica died, :class:`ReplicaError` for a served error)."""

    rid: str = "?"

    def submit(self, arrays, request_id: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               priority: Optional[str] = None):
        """``deadline_ms`` is the request's REMAINING budget as the
        router sees it at this attempt — a retry or hedge arrives at
        the replica with its true remaining slack, not a fresh
        deadline, so the replica's scheduler cannot double-spend time
        the router has already burned. ``priority`` picks the
        scheduler lane."""
        raise NotImplementedError

    def health(self) -> dict:
        raise NotImplementedError

    def metrics(self) -> Optional[dict]:
        """Flat ``name -> export`` metric payload for fleet federation
        (:mod:`mxnet_tpu.obswatch`), or None when the replica has no
        direct metrics path (e.g. a subprocess without a MetricsServer
        — those are scraped over HTTP instead)."""
        return None

    def alive(self) -> bool:
        raise NotImplementedError

    def in_flight(self) -> int:
        return 0

    def refresh_params(self, apply_fn=None, snapshot_dir=None):
        """Swap in new weights. ``apply_fn`` mutates the live server
        in-process (in-proc replicas only); ``snapshot_dir`` names a
        :class:`~mxnet_tpu.checkpoint.SnapshotStore` directory whose
        newest snapshot is streamed in delta-aware (only shards whose
        manifest digest changed move) — the only weight path that
        crosses a process boundary."""
        raise NotImplementedError

    def restart(self):
        raise NotImplementedError

    def kill(self):
        """Chaos hook: die like a crash, not like a shutdown."""
        raise NotImplementedError

    def close(self):
        raise NotImplementedError


class _RequestWaiter:
    """Adapts a :class:`mxnet_tpu.serving.Request` to the waiter
    protocol, mapping its errors onto the router's retry taxonomy."""

    def __init__(self, req):
        self._req = req

    def wait(self, timeout_s: float):
        try:
            return self._req.get(timeout_s)
        except MXNetError as e:
            if "timed out" in str(e):
                raise AttemptTimeout(str(e))
            raise ReplicaError(str(e))

    def done(self) -> bool:
        return self._req.done()

    def cancel(self):
        """Best-effort: the batcher may already be serving the work
        (idempotent, so the wasted dispatch is the only cost); we just
        stop waiting on it."""


class _PendingWaiter:
    """Parent-side waiter for one subprocess message id."""

    def __init__(self):
        self._done = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None

    def resolve(self, result):
        self._result = result
        self._done.set()

    def fail(self, err: BaseException):
        self._error = err
        self._done.set()

    def wait(self, timeout_s: float):
        if not self._done.wait(timeout_s):
            raise AttemptTimeout("replica response still pending after "
                                 "%.3fs" % timeout_s)
        if self._error is not None:
            raise self._error
        return self._result

    def done(self) -> bool:
        return self._done.is_set()

    def cancel(self):
        pass


class InProcReplica(Replica):
    """A replica backed by an in-process ``InferenceServer`` built by
    ``factory()``. Crash semantics are simulated (the server object is
    torn down and the handle refuses requests) — the subprocess backend
    is where a real SIGKILL is exercised."""

    def __init__(self, rid: str, factory: Callable[[], object]):
        self.rid = rid
        self._factory = factory
        self._srv = factory()
        self._dead = False
        self._t_up = time.monotonic()

    def alive(self) -> bool:
        srv = self._srv
        return (not self._dead and srv is not None
                and not getattr(srv, "closed", False))

    def submit(self, arrays, request_id: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               priority: Optional[str] = None,
               trace_ctx: Optional[dict] = None):
        if _faults.fires("replica_crash"):
            self.kill()
        srv = self._srv
        if not self.alive() or srv is None:
            raise ReplicaCrash("replica %s is down" % self.rid)
        if trace_ctx is not None:
            # kwarg only when traced: duck-typed test servers keep
            # their pre-trace submit signature
            return _RequestWaiter(srv.submit(
                arrays, request_id=request_id, deadline_ms=deadline_ms,
                priority=priority, trace_ctx=trace_ctx))
        return _RequestWaiter(srv.submit(arrays, request_id=request_id,
                                         deadline_ms=deadline_ms,
                                         priority=priority))

    def health(self) -> dict:
        srv = self._srv
        if not self.alive() or srv is None:
            raise ReplicaCrash("replica %s is down" % self.rid)
        probe = srv.scheduler.slo_probe()
        payload = {"status": "degraded" if probe else "ok",
                   "pid": os.getpid(),
                   "rank": _tracing.worker_rank(),
                   "uptime_s": round(time.monotonic() - self._t_up, 3)}
        payload.update(srv.health_info())
        if probe:
            payload["probes"] = {"serve_slo": probe}
        return payload

    def metrics(self) -> Optional[dict]:
        srv = self._srv
        if not self.alive() or srv is None:
            return None
        return srv.metrics_payload()

    def in_flight(self) -> int:
        srv = self._srv
        if not self.alive() or srv is None:
            return 0
        return srv.scheduler.in_flight()

    def refresh_params(self, apply_fn=None, snapshot_dir=None):
        srv = self._srv
        if not self.alive() or srv is None:
            raise ReplicaCrash("replica %s is down" % self.rid)
        if apply_fn is not None:
            apply_fn(srv)
        if snapshot_dir is not None:
            _refresh_from_store(srv, snapshot_dir)
        else:
            srv.refresh_params()

    def kill(self):
        self._dead = True
        srv, self._srv = self._srv, None
        if srv is not None:
            srv.close()

    def restart(self):
        self._srv = self._factory()
        self._dead = False
        self._t_up = time.monotonic()

    def close(self):
        self._dead = True
        srv, self._srv = self._srv, None
        if srv is not None:
            srv.close()


def _refresh_from_store(srv, snapshot_dir: str):
    """Stream the newest snapshot in ``snapshot_dir`` into a live
    server. The snapshot payload carries per-param sha256 digests, so
    the server's delta-aware refresh moves only the shards that
    actually changed since the last swap."""
    from .checkpoint import SnapshotStore

    found = SnapshotStore(snapshot_dir).load_latest()
    if found is None:
        raise MXNetError("snapshot dir %r holds no valid snapshot to "
                         "refresh from" % snapshot_dir)
    payload, _ = found
    srv.refresh_from_snapshot(payload)


def _resolve_factory(factory_ref: str) -> Callable[[], object]:
    """``"pkg.module:attr"`` -> the callable. A string ref (not a
    callable) crosses the spawn boundary without pickling closures."""
    import importlib

    mod_name, _, attr = factory_ref.partition(":")
    if not mod_name or not attr:
        raise MXNetError("factory ref %r is not 'module:attr'"
                         % factory_ref)
    fn = getattr(importlib.import_module(mod_name), attr, None)
    if not callable(fn):
        raise MXNetError("factory ref %r did not resolve to a callable"
                         % factory_ref)
    return fn


def _subprocess_replica_main(conn, factory_ref: str):
    """Child entry point: build the server from the factory ref, then
    serve the pipe protocol until ``stop`` or EOF. An injected
    ``replica_crash`` hard-exits mid-protocol — no goodbye message, the
    parent's reader sees the pipe break, exactly like a real kill."""
    srv = _resolve_factory(factory_ref)()
    t_up = time.monotonic()
    try:
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                break
            op, mid = msg[0], msg[1]
            if op == "infer":
                if _faults.fires("replica_crash"):
                    os._exit(23)
                # envelope: (op, mid, request_id, arrays, deadline_ms,
                # priority, trace_ctx) — the deadline is the router's
                # REMAINING budget for this attempt; old parents that
                # omit tail fields still work. A trace_ctx arms the
                # child's tracer lazily (programmatic enable() in the
                # parent does not cross the spawn boundary); traced
                # replies grow a 4th element with the harvested spans
                # + this process's clock epoch — old routers never
                # send a trace_ctx, so they never see a 4-tuple.
                tctx = msg[6] if len(msg) > 6 else None
                kw = {}
                if tctx is not None:
                    _dtrace.ensure_enabled()
                    kw["trace_ctx"] = tctx
                try:
                    out = srv.submit(
                        msg[3], request_id=msg[2],
                        deadline_ms=msg[4] if len(msg) > 4 else None,
                        priority=msg[5] if len(msg) > 5 else None,
                        **kw).get(60.0)
                    reply = ("ok", mid, [np.asarray(o) for o in out])
                    if tctx is not None:
                        reply += (_dtrace.harvest(tctx),)
                    conn.send(reply)
                except BaseException as e:   # noqa: BLE001 (report,
                    reply = ("err", mid,     # don't die)
                             "%s: %s" % (type(e).__name__, e))
                    if tctx is not None:
                        reply += (_dtrace.harvest(tctx),)
                    conn.send(reply)
            elif op == "health":
                try:
                    probe = srv.scheduler.slo_probe()
                    payload = {"status": "degraded" if probe else "ok",
                               "pid": os.getpid(),
                               "rank": _tracing.worker_rank(),
                               "uptime_s":
                                   round(time.monotonic() - t_up, 3)}
                    payload.update(srv.health_info())
                    if probe:
                        payload["probes"] = {"serve_slo": probe}
                    conn.send(("ok", mid, payload))
                except BaseException as e:   # noqa: BLE001
                    conn.send(("err", mid, str(e)))
            elif op == "refresh":
                try:
                    sdir = msg[2] if len(msg) > 2 else None
                    if sdir:
                        _refresh_from_store(srv, sdir)
                    else:
                        srv.refresh_params()
                    conn.send(("ok", mid, None))
                except BaseException as e:   # noqa: BLE001
                    conn.send(("err", mid, str(e)))
            elif op == "stop":
                conn.send(("ok", mid, None))
                break
    finally:
        srv.close()
        conn.close()


class SubprocessReplica(Replica):
    """A replica in its own interpreter: a spawned child builds the
    ``InferenceServer`` from ``factory_ref`` (``"module:attr"``) and
    serves a message protocol over a pipe. A daemon reader thread
    demultiplexes responses to per-message waiters; a broken pipe fails
    every pending waiter with :class:`ReplicaCrash` and marks the
    handle dead — crash *detection* is just reading the pipe.

    ``spawn`` is the default start method for the same reason the
    decode workers use it: forking next to a live TPU client duplicates
    its fds and locks.
    """

    def __init__(self, rid: str, factory_ref: str,
                 start_method: str = "spawn"):
        import multiprocessing

        self.rid = rid
        self._factory_ref = str(factory_ref)
        _resolve_factory(self._factory_ref)   # fail fast in the parent
        self._ctx = multiprocessing.get_context(start_method or "spawn")
        self._lock = threading.Lock()
        self._closed = False
        self._spawn()

    def _spawn(self):
        self._pending: Dict[str, _PendingWaiter] = {}
        self._dead = False
        self._conn, child_conn = self._ctx.Pipe()
        self._proc = self._ctx.Process(
            target=_subprocess_replica_main,
            args=(child_conn, self._factory_ref),
            name="mxtpu-fleet-%s" % self.rid, daemon=True)
        self._proc.start()
        child_conn.close()
        self._reader = threading.Thread(
            target=self._read_loop, args=(self._conn,),
            name="mxtpu-fleet-reader-%s" % self.rid, daemon=True)
        self._reader.start()

    def _read_loop(self, conn):
        try:
            while True:
                try:
                    msg = conn.recv()
                except (EOFError, OSError):
                    break
                # replies are (kind, mid, payload) — traced ones append
                # a span payload the tracer clock-aligns and merges
                # BEFORE the waiter resolves (the root may finish right
                # after)
                kind, mid, payload = msg[0], msg[1], msg[2]
                if len(msg) > 3 and msg[3]:
                    trc = _dtrace._TRACER
                    if trc is not None:
                        trc.absorb(msg[3])
                with self._lock:
                    w = self._pending.pop(mid, None)
                if w is None:
                    continue
                if kind == "ok":
                    w.resolve(payload)
                else:
                    w.fail(ReplicaError("replica %s: %s"
                                        % (self.rid, payload)))
        except Exception:   # noqa: BLE001 — an unexpected reader death
            # (malformed reply, absorb bug) is NOT an EOF-equivalent:
            # count it so it pages instead of masquerading as a crash
            _tel.inc("fleet.reader_errors")
            _log.exception("fleet reader for %s died unexpectedly",
                           self.rid)
        self._mark_dead()

    def _mark_dead(self):
        with self._lock:
            self._dead = True
            pending = list(self._pending.values())
            self._pending.clear()
        for w in pending:
            w.fail(ReplicaCrash("replica %s died mid-request"
                                % self.rid))

    def _send(self, op: str, payload=None) -> _PendingWaiter:
        w = _PendingWaiter()
        mid = uuid.uuid4().hex
        broke = False
        with self._lock:
            if self._dead or not self._proc.is_alive():
                broke = True
            else:
                self._pending[mid] = w
                try:
                    self._conn.send((op, mid) + (payload or ()))
                except (OSError, BrokenPipeError):
                    # narrowed from the historical (OSError,
                    # BrokenPipeError, ValueError): a ValueError here is
                    # an oversized/unpicklable payload — a caller bug,
                    # not a dead pipe — and masking it as ReplicaCrash
                    # sent the router respawning a healthy replica
                    self._pending.pop(mid, None)
                    broke = True
                except ValueError:
                    # surfaced to the caller as the bug it is
                    self._pending.pop(mid, None)
                    raise
        if broke:
            self._mark_dead()
            raise ReplicaCrash("replica %s is down" % self.rid)
        return w

    def alive(self) -> bool:
        return (not self._dead and not self._closed
                and self._proc.is_alive())

    def submit(self, arrays, request_id: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               priority: Optional[str] = None,
               trace_ctx: Optional[dict] = None):
        arrays = [np.asarray(a) for a in arrays]
        payload = (request_id, arrays, deadline_ms, priority)
        if trace_ctx is not None:
            # appended, never inserted: old children index the tail
            # conditionally and ignore anything past what they know
            payload += (trace_ctx,)
        return self._send("infer", payload)

    def health(self, timeout_s: float = 5.0) -> dict:
        return self._send("health").wait(timeout_s)

    def in_flight(self) -> int:
        with self._lock:
            return len(self._pending)

    def refresh_params(self, apply_fn=None, snapshot_dir=None,
                       timeout_s: float = 60.0):
        # apply_fn cannot cross the process boundary; the child's own
        # factory/checkpoint path owns its params and ``refresh``
        # repacks them (serve-while-training delivers new weights via
        # the checkpoint dir, not a closure)
        if apply_fn is not None:
            raise MXNetError("apply_fn is not supported for subprocess "
                             "replicas; ship params via checkpoint")
        payload = (snapshot_dir,) if snapshot_dir else None
        self._send("refresh", payload).wait(timeout_s)

    def kill(self):
        """SIGKILL the child (chaos): pending requests fail with
        ReplicaCrash once the reader sees the pipe break."""
        self._proc.kill()
        self._proc.join(5.0)

    def restart(self):
        self._teardown(graceful=False)
        self._spawn()
        self._closed = False

    def _teardown(self, graceful: bool = True):
        if graceful:
            try:
                self._send("stop").wait(5.0)
            except FleetError:
                pass
        self._proc.join(2.0)
        if self._proc.is_alive():
            self._proc.kill()
            self._proc.join(5.0)
        try:
            self._conn.close()
        except OSError:
            pass
        self._reader.join(2.0)
        self._mark_dead()

    def close(self):
        if self._closed:
            return
        self._closed = True
        self._teardown(graceful=True)


def in_process(factory: Callable[[], object]) -> Callable[[str], Replica]:
    """Replica-factory adapter: ``factory()`` builds an
    ``InferenceServer``; each router slot gets its own."""
    return lambda rid: InProcReplica(rid, factory)


def in_subprocess(factory_ref: str,
                  start_method: str = "spawn") -> Callable[[str], Replica]:
    """Replica-factory adapter for subprocess replicas;
    ``factory_ref`` is ``"module:attr"`` resolved inside the child."""
    return lambda rid: SubprocessReplica(rid, factory_ref, start_method)


# ---------------------------------------------------------------------------
# socket replicas (netwire transport)
# ---------------------------------------------------------------------------

def _socket_replica_main(port_conn, factory_ref: str):
    """Child entry point for a socket replica: build the server from
    the factory ref, serve the netwire frame protocol on an ephemeral
    loopback port (reported back through ``port_conn``), run until a
    ``stop`` frame or the parent kills us. The frame envelope mirrors
    the pipe protocol — op/mid plus a metadata dict — so the reply
    taxonomy ("ok"/"err", dtrace harvest appended when traced) is
    identical; only the bytes underneath changed."""
    from . import netwire as _netwire

    srv = _resolve_factory(factory_ref)()
    t_up = time.monotonic()
    stop = threading.Event()

    def handler(frame, respond):
        op, meta = frame.op, frame.meta
        if op == "infer":
            if _faults.fires("replica_crash"):
                os._exit(23)
            tctx = frame.tctx
            kw = {}
            if tctx is not None:
                _dtrace.ensure_enabled()
                kw["trace_ctx"] = tctx
            try:
                out = srv.submit(
                    frame.arrays, request_id=meta.get("req"),
                    deadline_ms=meta.get("deadline_ms"),
                    priority=meta.get("priority"), **kw).get(60.0)
                rmeta = {}
                if tctx is not None:
                    rmeta["dtrace"] = _dtrace.harvest(tctx)
                respond("ok", [np.asarray(o) for o in out], rmeta)
            except BaseException as e:   # noqa: BLE001 (report,
                rmeta = {"error": "%s: %s"   # don't die)
                         % (type(e).__name__, e)}
                if tctx is not None:
                    rmeta["dtrace"] = _dtrace.harvest(tctx)
                respond("err", (), rmeta)
        elif op == "health":
            try:
                probe = srv.scheduler.slo_probe()
                payload = {"status": "degraded" if probe else "ok",
                           "pid": os.getpid(),
                           "rank": _tracing.worker_rank(),
                           "uptime_s":
                               round(time.monotonic() - t_up, 3)}
                payload.update(srv.health_info())
                if probe:
                    payload["probes"] = {"serve_slo": probe}
                respond("ok", (), {"health": payload})
            except BaseException as e:   # noqa: BLE001
                respond("err", (), {"error": str(e)})
        elif op == "refresh":
            try:
                sdir = meta.get("snapshot_dir") if meta else None
                if sdir:
                    _refresh_from_store(srv, sdir)
                else:
                    srv.refresh_params()
                respond("ok")
            except BaseException as e:   # noqa: BLE001
                respond("err", (), {"error": str(e)})
        elif op == "stop":
            respond("ok")
            stop.set()
        else:
            respond("err", (), {"error": "unknown op %r" % (op,)})

    wire = _netwire.WireServer(handler, "127.0.0.1", 0,
                               name="replica-%d" % os.getpid())
    try:
        port_conn.send(wire.port)
        port_conn.close()
        while not stop.wait(0.5):
            pass
    finally:
        wire.close()
        srv.close()


class _SocketWaiter:
    """Adapts a netwire reply waiter to the router's waiter protocol,
    mapping the wire taxonomy onto the retry taxonomy."""

    def __init__(self, waiter, rid: str):
        self._w = waiter
        self.rid = rid

    def wait(self, timeout_s: float):
        from . import netwire as _netwire

        try:
            frame = self._w.wait(timeout_s)
        except _netwire.WireTimeout as e:
            # forget the mid: a fault-dropped frame's reply never comes
            self._w.cancel()
            raise AttemptTimeout(str(e))
        except _netwire.WirePeerLost as e:
            raise ReplicaCrash("replica %s died mid-request (%s)"
                               % (self.rid, e))
        except _netwire.WireError as e:
            raise ReplicaError("replica %s wire error: %s"
                               % (self.rid, e))
        if frame.op != "ok":
            raise ReplicaError("replica %s: %s"
                               % (self.rid,
                                  frame.meta.get("error", frame.op)))
        return frame.arrays

    def done(self) -> bool:
        return self._w.done()

    def cancel(self):
        self._w.cancel()


class SocketReplica(Replica):
    """A replica across the network fabric: the same spawned child as
    :class:`SubprocessReplica`, but serving netwire frames on a
    loopback TCP port instead of a pickled pipe — the single-host
    rehearsal of a cross-host fleet. The pooled :class:`WireClient`
    gives the router ``MXNET_TPU_WIRE_POOL``-way concurrency per
    replica; crash detection is the connection reset failing in-flight
    waiters with :class:`ReplicaCrash`, and the monitor's respawn path
    works unchanged (a restart spawns a fresh child on a fresh port).

    ``host``/``port`` may also point at an already-running remote
    ``_socket_replica_main``-style server (no child lifecycle then:
    ``kill``/``restart`` raise, and ``close`` only drops connections).
    """

    def __init__(self, rid: str, factory_ref: Optional[str] = None,
                 start_method: str = "spawn",
                 host: str = "127.0.0.1", port: Optional[int] = None):
        from . import netwire as _netwire

        self.rid = rid
        self._netwire = _netwire
        self._factory_ref = None if factory_ref is None else str(factory_ref)
        self._host = host
        self._closed = False
        self._proc = None
        self._client: Optional[_netwire.WireClient] = None
        if port is not None:
            self._port = int(port)
            self._client = _netwire.WireClient(host, self._port, peer=rid)
            self._ctx = None
            return
        if self._factory_ref is None:
            raise MXNetError("SocketReplica needs a factory_ref to "
                             "spawn, or an explicit port to connect to")
        _resolve_factory(self._factory_ref)   # fail fast in the parent
        import multiprocessing

        self._ctx = multiprocessing.get_context(start_method or "spawn")
        self._spawn()

    def _spawn(self):
        port_conn, child_conn = self._ctx.Pipe()
        self._proc = self._ctx.Process(
            target=_socket_replica_main,
            args=(child_conn, self._factory_ref),
            name="mxtpu-fleet-%s" % self.rid, daemon=True)
        self._proc.start()
        child_conn.close()
        # the child reports its ephemeral port once the listener is up;
        # a child that dies first (bad factory) must not hang us
        if not port_conn.poll(30.0):
            port_conn.close()
            self._proc.join(1.0)
            raise MXNetError("socket replica %s never reported a port"
                             % self.rid)
        try:
            self._port = int(port_conn.recv())
        except (EOFError, OSError):
            port_conn.close()
            raise MXNetError("socket replica %s died before reporting "
                             "a port" % self.rid)
        port_conn.close()
        self._client = self._netwire.WireClient(self._host, self._port,
                                                peer=self.rid)

    def alive(self) -> bool:
        if self._closed or self._client is None:
            return False
        if self._proc is not None:
            return self._proc.is_alive()
        return self._client.alive()

    def submit(self, arrays, request_id: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               priority: Optional[str] = None,
               trace_ctx: Optional[dict] = None):
        if not self.alive():
            raise ReplicaCrash("replica %s is down" % self.rid)
        meta = {"req": request_id, "deadline_ms": deadline_ms,
                "priority": priority}
        try:
            w = self._client.request(
                "infer", [np.asarray(a) for a in arrays], meta,
                trace_ctx=trace_ctx)
        except self._netwire.WireError as e:
            raise ReplicaCrash("replica %s is unreachable: %s"
                               % (self.rid, e))
        return _SocketWaiter(w, self.rid)

    def health(self, timeout_s: float = 5.0) -> dict:
        try:
            frame = self._client.call("health", timeout_s=timeout_s)
        except self._netwire.WireTimeout as e:
            raise AttemptTimeout(str(e))
        except self._netwire.WireError as e:
            raise ReplicaCrash("replica %s is unreachable: %s"
                               % (self.rid, e))
        if frame.op != "ok":
            raise ReplicaError("replica %s: %s"
                               % (self.rid, frame.meta.get("error")))
        return frame.meta.get("health") or {}

    def in_flight(self) -> int:
        return 0 if self._client is None else self._client.pending_count()

    def wire_stats(self) -> dict:
        """Per-peer transport rollup (frames/bytes/rtt/reconnects/
        stalls), the shape ``trace_report --view wire`` renders."""
        return {} if self._client is None else self._client.stats()

    def refresh_params(self, apply_fn=None, snapshot_dir=None,
                       timeout_s: float = 60.0):
        if apply_fn is not None:
            raise MXNetError("apply_fn is not supported for socket "
                             "replicas; ship params via checkpoint")
        meta = {"snapshot_dir": snapshot_dir} if snapshot_dir else None
        try:
            frame = self._client.call("refresh", meta=meta,
                                      timeout_s=timeout_s)
        except self._netwire.WireTimeout as e:
            raise AttemptTimeout(str(e))
        except self._netwire.WireError as e:
            raise ReplicaCrash("replica %s is unreachable: %s"
                               % (self.rid, e))
        if frame.op != "ok":
            raise ReplicaError("replica %s refresh failed: %s"
                               % (self.rid, frame.meta.get("error")))

    def kill(self):
        """SIGKILL the child (chaos): in-flight requests fail with
        ReplicaCrash as their connections reset."""
        if self._proc is None:
            raise MXNetError("cannot kill a remote socket replica %s"
                             % self.rid)
        self._proc.kill()
        self._proc.join(5.0)

    def restart(self):
        if self._proc is None:
            raise MXNetError("cannot restart a remote socket replica %s"
                             % self.rid)
        self._teardown(graceful=False)
        self._spawn()
        self._closed = False

    def _teardown(self, graceful: bool = True):
        if graceful and self._client is not None:
            try:
                self._client.call("stop", timeout_s=5.0)
            except self._netwire.WireError:
                pass
        if self._client is not None:
            self._client.close()
            self._client = None
        if self._proc is not None:
            self._proc.join(2.0)
            if self._proc.is_alive():
                self._proc.kill()
                self._proc.join(5.0)

    def close(self):
        if self._closed:
            return
        self._closed = True
        self._teardown(graceful=True)


def in_socket(factory_ref: str,
              start_method: str = "spawn") -> Callable[[str], Replica]:
    """Replica-factory adapter for socket replicas: each router slot
    spawns a child serving netwire frames on its own loopback port.
    Retries, hedges, breakers, respawn, and rolling swaps work
    unchanged — the router only ever sees the :class:`Replica`
    protocol."""
    return lambda rid: SocketReplica(rid, factory_ref, start_method)


def demo_server_factory():
    """A tiny deterministic MLP behind an ``InferenceServer`` — the
    spawn-resolvable factory (``"mxnet_tpu.fleet:demo_server_factory"``)
    the subprocess- and socket-replica tests build replicas from.
    Params are seeded half-integers over integer inputs (the serving
    tests' exact-arithmetic regime), so replica parity is bit-exact."""
    import mxnet_tpu as mx
    from .module import Module
    from .serving import InferenceServer

    dim, classes, hid = 8, 4, 16
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=hid, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    batch = 8
    arg_shapes, _, _ = net.infer_shape(data=(batch, dim),
                                       softmax_label=(batch,))
    rng = np.random.RandomState(3)
    params = {name: mx.nd.array(
        (rng.randint(-2, 3, shape) * 0.5).astype(np.float32))
        for name, shape in zip(net.list_arguments(), arg_shapes)
        if name not in ("data", "softmax_label")}
    mod = Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (batch, dim))],
             label_shapes=[("softmax_label", (batch,))],
             for_training=False)
    mod.init_params(initializer=None, arg_params=params, aux_params={})
    return InferenceServer(mod, top_k=0, max_batch=batch,
                           max_wait_ms=0.5, buckets=[batch], slo_ms=0.0,
                           port=None)


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

class _Entry:
    __slots__ = ("replica", "breaker", "state", "inflight", "served",
                 "failures", "degraded_ticks")

    def __init__(self, replica: Replica, breaker: CircuitBreaker):
        self.replica = replica
        self.breaker = breaker
        self.state = "up"            # up | draining | dead
        self.inflight = 0
        self.served = 0
        self.failures = 0
        self.degraded_ticks = 0


class FleetRouter:
    """Spread requests over N replicas; keep them succeeding while
    replicas die, stall, or swap weights. See the module docstring for
    the mechanism inventory; every knob falls back to its
    ``MXNET_TPU_FLEET_*`` declaration.

    ``factory(rid) -> Replica`` builds one replica per slot (use
    :func:`in_process` / :func:`in_subprocess`). ``clock``/``sleep``
    are injectable so the retry/breaker math is testable with a fake
    clock and zero real waiting.
    """

    def __init__(self, factory: Callable[[str], Replica],
                 n_replicas: Optional[int] = None, *,
                 deadline_ms: Optional[float] = None,
                 attempt_timeout_ms: Optional[float] = None,
                 retries: Optional[int] = None,
                 backoff_ms: Optional[float] = None,
                 hedge: Optional[bool] = None,
                 breaker_fails: Optional[int] = None,
                 breaker_cooldown_ms: Optional[float] = None,
                 auto_respawn: bool = True,
                 autoscale: bool = False,
                 min_replicas: Optional[int] = None,
                 max_replicas: Optional[int] = None,
                 scale_down_ticks: int = 200,
                 health_interval_s: float = 0.05,
                 max_workers: int = 16,
                 session_vnodes: int = 32,
                 seed: int = 0,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep):
        self._factory = factory
        self._deadline_s = float(
            _env.get("MXNET_TPU_FLEET_DEADLINE_MS")
            if deadline_ms is None else deadline_ms) / 1e3
        self._attempt_s = float(
            _env.get("MXNET_TPU_FLEET_ATTEMPT_TIMEOUT_MS")
            if attempt_timeout_ms is None else attempt_timeout_ms) / 1e3
        self._retries = int(_env.get("MXNET_TPU_FLEET_RETRIES")
                            if retries is None else retries)
        self._backoff_s = float(
            _env.get("MXNET_TPU_FLEET_BACKOFF_MS")
            if backoff_ms is None else backoff_ms) / 1e3
        self._hedge = bool(_env.get("MXNET_TPU_FLEET_HEDGE")
                           if hedge is None else hedge)
        self._breaker_fails = breaker_fails
        self._breaker_cooldown_s = (
            None if breaker_cooldown_ms is None
            else float(breaker_cooldown_ms) / 1e3)
        self._auto_respawn = bool(auto_respawn)
        self._autoscale = bool(autoscale)
        self._min_replicas = int(
            _env.get("MXNET_TPU_FLEET_MIN_REPLICAS")
            if min_replicas is None else min_replicas)
        self._max_replicas = int(
            _env.get("MXNET_TPU_FLEET_MAX_REPLICAS")
            if max_replicas is None else max_replicas)
        self._scale_down_ticks = int(scale_down_ticks)
        self._vnodes = int(session_vnodes)
        self._clock = clock
        self._sleep = sleep
        self._rng = Random(seed)
        self._rng_lock = threading.Lock()

        self._rlock = threading.RLock()
        self._entries: Dict[str, _Entry] = {}
        self._ring: List[Tuple[int, str]] = []
        self._rid_seq = 0
        self._lat: deque = deque(maxlen=512)
        # router-view latency histogram for fleet federation: what the
        # CLIENT experiences (queueing + dispatch + wire), as opposed
        # to each scheduler's enqueue-to-done view — obswatch headlines
        # fleet percentiles from this series
        self._lat_hist = _tel.Histogram("router.request_ms")
        self._events: deque = deque(maxlen=1024)
        self._counters: Dict[str, int] = {}
        self._t0 = self._clock()
        self._healthy_ticks = 0
        self._closed = False

        n = int(_env.get("MXNET_TPU_FLEET_REPLICAS")
                if n_replicas is None else n_replicas)
        if n < 1:
            raise MXNetError("a fleet needs at least one replica")
        for _ in range(n):
            self.add_replica()

        self._pool = ThreadPoolExecutor(
            max_workers=int(max_workers),
            thread_name_prefix="mxtpu-fleet-router")
        self._stop = threading.Event()
        self._interval = float(health_interval_s)
        self._monitor_thread = threading.Thread(
            target=self._monitor, name="mxtpu-fleet-monitor", daemon=True)
        self._monitor_thread.start()
        _log.info("fleet up: %d replicas, deadline=%.0fms attempt=%.0fms "
                  "retries=%d hedge=%s", n, self._deadline_s * 1e3,
                  self._attempt_s * 1e3, self._retries, self._hedge)

    # -- bookkeeping -------------------------------------------------------
    def _count(self, name: str, n: int = 1):
        with self._rlock:
            self._counters[name] = self._counters.get(name, 0) + n
        _tel.inc("fleet.%s" % name, n)

    def _event(self, etype: str, rid: Optional[str] = None, **extra):
        ev = {"t_s": round(self._clock() - self._t0, 4), "type": etype}
        if rid is not None:
            ev["rid"] = rid
        if extra:
            ev.update(extra)
        with self._rlock:
            self._events.append(ev)
        _log.debug("fleet event: %s", ev)

    def _new_breaker(self) -> CircuitBreaker:
        return CircuitBreaker(fail_threshold=self._breaker_fails,
                              cooldown_s=self._breaker_cooldown_s,
                              clock=self._clock)

    # -- membership --------------------------------------------------------
    def _hash(self, key: str) -> int:
        return int(hashlib.md5(key.encode()).hexdigest()[:8], 16)

    def _rebuild_ring(self):
        ring = []
        for rid, e in self._entries.items():
            if e.state != "up":
                continue
            for v in range(self._vnodes):
                ring.append((self._hash("%s#%d" % (rid, v)), rid))
        ring.sort()
        self._ring = ring

    def add_replica(self) -> str:
        with self._rlock:
            self._rid_seq += 1
            rid = "r%d" % self._rid_seq
        replica = self._factory(rid)   # may be slow; not under the lock
        with self._rlock:
            self._entries[rid] = _Entry(replica, self._new_breaker())
            self._rebuild_ring()
        self._event("replica_added", rid)
        return rid

    def remove_replica(self, rid: str, drain_timeout_s: float = 30.0):
        """Graceful drain-then-stop: unroute, wait for in-flight work
        to finish, then close the replica and forget it."""
        with self._rlock:
            e = self._entries.get(rid)
            if e is None:
                return
            e.state = "draining"
            self._rebuild_ring()
        self._await_drain(e, drain_timeout_s)
        e.replica.close()
        with self._rlock:
            self._entries.pop(rid, None)
            self._rebuild_ring()
        self._event("replica_removed", rid)

    def _await_drain(self, e: _Entry, timeout_s: float):
        t_end = self._clock() + float(timeout_s)
        while self._clock() < t_end:
            with self._rlock:
                inflight = e.inflight
            if inflight == 0 and e.replica.in_flight() == 0:
                return
            self._sleep(0.002)
        _log.warning("fleet drain timed out with %d in flight",
                     e.inflight)

    def kill_replica(self, rid: str):
        """Chaos hook: crash (not drain) a replica; the monitor's
        crash-detection/respawn path takes it from there."""
        with self._rlock:
            e = self._entries.get(rid)
        if e is None:
            raise MXNetError("no replica %r" % rid)
        e.replica.kill()
        self._event("replica_killed", rid)

    def replica_ids(self) -> List[str]:
        with self._rlock:
            return list(self._entries)

    def replicas(self) -> List[Tuple[str, Replica]]:
        """(rid, replica) pairs — the obswatch scraper's target list."""
        with self._rlock:
            return [(rid, e.replica) for rid, e in self._entries.items()]

    def metrics_payload(self) -> dict:
        """Router-tier metric series for fleet federation: the
        client-view latency histogram plus the request counters."""
        with self._rlock:
            counters = dict(self._counters)
        out = {"router.request_ms":
               self._lat_hist.export(include_sample=True)}
        for k in ("served", "retries", "hedges", "recovered_requests"):
            out["router." + k] = int(counters.get(k, 0))
        return out

    # -- routing -----------------------------------------------------------
    def _routable(self, rid: str, e: _Entry, exclude) -> bool:
        return (e.state == "up" and rid not in exclude
                and e.replica.alive())

    def _pick(self, session: Optional[str], exclude=()) -> Tuple[str, _Entry]:
        """Choose a replica: ring walk from the session hash when
        affinity is requested, else least-in-flight; the first
        candidate whose breaker admits the request wins."""
        with self._rlock:
            if session is not None and self._ring:
                start = bisect.bisect_left(
                    self._ring, (self._hash(session), ""))
                ordered, seen = [], set()
                for i in range(len(self._ring)):
                    _, rid = self._ring[(start + i) % len(self._ring)]
                    if rid in seen:
                        continue
                    seen.add(rid)
                    e = self._entries.get(rid)
                    if e is not None and self._routable(rid, e, exclude):
                        ordered.append((rid, e))
            else:
                ordered = sorted(
                    ((rid, e) for rid, e in self._entries.items()
                     if self._routable(rid, e, exclude)),
                    key=lambda kv: (kv[1].inflight, kv[0]))
            for rid, e in ordered:
                if e.breaker.allow():
                    return rid, e
            states = {rid: (e.state, e.breaker.state)
                      for rid, e in self._entries.items()}
        raise NoReplicaAvailable("no routable replica (states=%s)"
                                 % states)

    def _hedge_after_s(self) -> Optional[float]:
        with self._rlock:
            lat = sorted(self._lat)
        if len(lat) < 20:
            return None
        return lat[min(len(lat) - 1, int(0.95 * len(lat)))]

    # -- request path ------------------------------------------------------
    def submit(self, arrays, session: Optional[str] = None,
               request_id: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               priority: Optional[str] = None) -> Future:
        """Route one request; returns a Future resolving to the result
        arrays (or raising a :class:`FleetError` once the deadline
        budget is spent). The deadline is ONE total budget: every
        attempt (retry or hedge) ships the remaining slack to the
        replica in the request envelope, so the replica's scheduler
        never re-starts the clock. ``priority`` picks the replica
        scheduler's lane (interactive/batch)."""
        if self._closed:
            raise MXNetError("FleetRouter is closed")
        rid = request_id or uuid.uuid4().hex
        deadline_s = (self._deadline_s if deadline_ms is None
                      else float(deadline_ms) / 1e3)
        self._count("requests")
        root = None
        trc = _dtrace._TRACER   # disabled cost: this one None check
        if trc is not None:
            root = trc.start_trace(
                "fleet.request", request_id=rid,
                tags={"deadline_ms": round(deadline_s * 1e3, 1),
                      "priority": priority or "interactive"})
        t_sub = self._clock()
        fut = self._pool.submit(self._serve, arrays, session, rid,
                                deadline_s, priority, root)

        def _observe_latency(f):
            # router-view latency = submit to completion, pool queueing
            # included — the same interval the client experiences, so
            # obswatch's federated fleet p99 matches what callers see
            if f.cancelled() or f.exception() is not None:
                return
            self._lat_hist.observe((self._clock() - t_sub) * 1e3)

        fut.add_done_callback(_observe_latency)
        return fut

    def infer(self, arrays, session: Optional[str] = None,
              request_id: Optional[str] = None,
              deadline_ms: Optional[float] = None,
              priority: Optional[str] = None,
              timeout: Optional[float] = None):
        """Synchronous convenience: submit + wait."""
        deadline_s = (self._deadline_s if deadline_ms is None
                      else float(deadline_ms) / 1e3)
        return self.submit(arrays, session=session, request_id=request_id,
                           deadline_ms=deadline_ms,
                           priority=priority).result(
                               deadline_s + 5.0 if timeout is None
                               else timeout)

    def _serve(self, arrays, session, request_id, deadline_s,
               priority=None, root=None):
        if root is None:
            return self._serve_loop(arrays, session, request_id,
                                    deadline_s, priority, None)
        try:
            result = self._serve_loop(arrays, session, request_id,
                                      deadline_s, priority, root)
        except BaseException as e:
            _dtrace.finish_root(root, error=e)
            raise
        _dtrace.finish_root(root)
        return result

    def _serve_loop(self, arrays, session, request_id, deadline_s,
                    priority, root):
        t_start = self._clock()
        attempt = 0
        exclude: set = set()
        last_err: Optional[BaseException] = None
        while True:
            remaining = deadline_s - (self._clock() - t_start)
            if remaining <= 0:
                self._count("deadline_exceeded")
                raise DeadlineExceeded(
                    "request %s exhausted its %.0fms deadline after %d "
                    "attempts (last error: %s)"
                    % (request_id, deadline_s * 1e3, attempt, last_err))
            if attempt >= self._retries:
                self._count("retries_exhausted")
                raise FleetError(
                    "request %s failed after %d attempts: %s"
                    % (request_id, attempt, last_err))
            try:
                rid, entry = self._pick(session, exclude)
            except NoReplicaAvailable as e:
                # nothing routable *right now* — a respawn or a breaker
                # cooldown can change that within the budget
                last_err = e
                exclude.clear()
                self._backoff_sleep(attempt, t_start, deadline_s)
                attempt += 1
                continue
            t_a = self._clock()
            aspan = None
            if root is not None:
                aspan = root._tracer.start_span(
                    "fleet.attempt", root,
                    tags={"attempt": attempt, "replica": rid,
                          "breaker": entry.breaker.state})
            try:
                result = self._attempt(rid, entry, arrays, request_id,
                                       min(self._attempt_s, remaining),
                                       priority, root, aspan)
            except (FleetError, MXNetError) as e:
                if aspan is not None:
                    aspan.finish(won=False, error="%s: %s"
                                 % (type(e).__name__, e))
                last_err = e
                with self._rlock:
                    entry.failures += 1
                if entry.breaker.record_failure():
                    self._count("breaker_trips")
                    self._event("breaker_open", rid)
                self._count("retries")
                exclude.add(rid)
                if len(exclude) >= len(self.replica_ids()):
                    exclude = {rid}
                self._backoff_sleep(attempt, t_start, deadline_s)
                attempt += 1
                continue
            if aspan is not None:
                # a hedge that won elsewhere already finished this
                # span as abandoned; finish() is first-writer-wins
                aspan.finish(won=True)
            lat_s = self._clock() - t_a
            with self._rlock:
                entry.served += 1
                self._lat.append(lat_s)
            entry.breaker.record_success()
            self._count("served")
            if attempt:
                self._count("recovered_requests")
            return result

    def _backoff_sleep(self, attempt, t_start, deadline_s):
        with self._rng_lock:
            delay = backoff_delay_s(attempt, self._backoff_s, self._rng)
        remaining = deadline_s - (self._clock() - t_start)
        if remaining > 0:
            self._sleep(min(delay, remaining))

    def _attempt(self, rid, entry, arrays, request_id, timeout_s,
                 priority=None, root=None, aspan=None):
        with self._rlock:
            entry.inflight += 1
        try:
            # the envelope deadline is exactly this attempt's timeout:
            # the remaining total budget, already net of earlier
            # attempts — a retried request cannot double-spend slack
            w = entry.replica.submit(
                arrays, request_id=request_id,
                deadline_ms=timeout_s * 1e3, priority=priority,
                **({"trace_ctx": aspan.ctx()} if aspan is not None
                   else {}))
            hedge_after = self._hedge_after_s() if self._hedge else None
            if hedge_after is None or hedge_after >= timeout_s:
                return w.wait(timeout_s)
            try:
                return w.wait(hedge_after)
            except AttemptTimeout:
                pass
            return self._hedged_wait(rid, w, arrays, request_id,
                                     timeout_s - hedge_after, priority,
                                     root, aspan)
        finally:
            with self._rlock:
                entry.inflight -= 1

    def _hedged_wait(self, rid, w1, arrays, request_id, remaining_s,
                     priority=None, root=None, aspan=None):
        """The attempt is past p95: duplicate it elsewhere (same
        request-id — the replica dedupes; same REMAINING deadline — the
        hedge doesn't get fresh slack), first response wins, the loser
        is abandoned."""
        self._count("hedges")
        try:
            rid2, e2 = self._pick(None, exclude={rid})
        except NoReplicaAvailable:
            return w1.wait(remaining_s)   # nowhere to hedge to
        hspan = None
        if root is not None:
            root.tag(hedged=True)
            hspan = root._tracer.start_span(
                "fleet.attempt", root,
                tags={"attempt": (aspan.tags.get("attempt", 0)
                                  if aspan is not None else 0),
                      "replica": rid2, "hedge": True,
                      "breaker": e2.breaker.state})
        with self._rlock:
            e2.inflight += 1
        try:
            try:
                w2 = e2.replica.submit(
                    arrays, request_id=request_id,
                    deadline_ms=remaining_s * 1e3, priority=priority,
                    **({"trace_ctx": hspan.ctx()} if hspan is not None
                       else {}))
            except FleetError as e:
                if hspan is not None:
                    hspan.finish(won=False, error="%s: %s"
                                 % (type(e).__name__, e))
                return w1.wait(remaining_s)
            waiters = {rid: w1, rid2: w2}
            t_end = self._clock() + remaining_s
            last: BaseException = AttemptTimeout(
                "hedged attempt timed out after %.3fs" % remaining_s)
            while waiters and self._clock() < t_end:
                for wrid, w in list(waiters.items()):
                    try:
                        res = w.wait(0.002)
                    except AttemptTimeout:
                        continue
                    except FleetError as e:
                        last = e
                        del waiters[wrid]
                        continue
                    if wrid == rid2:
                        self._count("hedge_wins")
                        with self._rlock:
                            e2.served += 1
                        e2.breaker.record_success()
                        if hspan is not None:
                            hspan.finish(won=True)
                        if aspan is not None:
                            aspan.finish(won=False, abandoned=True)
                        w1.cancel()
                    else:
                        if hspan is not None:
                            hspan.finish(won=False, abandoned=True)
                        w2.cancel()
                    return res
            if hspan is not None:
                hspan.finish(won=False,
                             error="AttemptTimeout: %s" % last)
            raise last
        finally:
            with self._rlock:
                e2.inflight -= 1

    # -- health / lifecycle loop -------------------------------------------
    def _monitor(self):
        while not self._stop.wait(self._interval):
            try:
                self._monitor_tick()
            except Exception:   # noqa: BLE001 (the monitor must outlive
                _log.exception("fleet monitor tick failed")   # anything)

    def _monitor_tick(self):
        with self._rlock:
            entries = list(self._entries.items())
        down = degraded = open_breakers = 0
        for rid, e in entries:
            if e.state == "draining":
                continue
            if not e.replica.alive():
                if e.state != "dead":
                    with self._rlock:
                        e.state = "dead"
                        self._rebuild_ring()
                    self._event("replica_dead", rid)
                    self._count("replica_crashes")
                if self._auto_respawn:
                    try:
                        e.replica.restart()
                    except Exception as ex:   # noqa: BLE001 (retry next
                        _log.warning("respawn of %s failed: %s",   # tick)
                                     rid, ex)
                        down += 1
                        continue
                    with self._rlock:
                        e.state = "up"
                        e.breaker = self._new_breaker()
                        self._rebuild_ring()
                    self._event("replica_respawned", rid)
                    self._count("respawns")
                else:
                    down += 1
                continue
            try:
                h = e.replica.health()
            except FleetError:
                continue   # died between alive() and health(); next tick
            except Exception as ex:   # noqa: BLE001
                _log.debug("health of %s failed: %s", rid, ex)
                continue
            if h.get("status") != "ok":
                degraded += 1
                e.degraded_ticks += 1
            else:
                e.degraded_ticks = 0
            if e.breaker.state != CircuitBreaker.CLOSED:
                open_breakers += 1
        if down or open_breakers:
            # surface through the anomaly plane: FleetHealthDetector
            # turns this record into a fleet_degraded event
            _tracing.record_step(0.0, extra={
                "fleet_down": down, "breaker_open": open_breakers,
                "fleet_size": len(entries)})
        if self._autoscale:
            self._autoscale_tick(degraded)

    def _autoscale_tick(self, degraded: int):
        with self._rlock:
            n_up = sum(1 for e in self._entries.values()
                       if e.state == "up")
        if degraded and n_up < self._max_replicas:
            self._healthy_ticks = 0
            rid = self.add_replica()
            self._event("scale_up", rid, fleet_size=n_up + 1)
            self._count("scale_ups")
            return
        if degraded or n_up <= self._min_replicas:
            self._healthy_ticks = 0
            return
        self._healthy_ticks += 1
        if self._healthy_ticks >= self._scale_down_ticks:
            self._healthy_ticks = 0
            with self._rlock:
                victims = sorted(
                    ((e.inflight, rid) for rid, e in
                     self._entries.items() if e.state == "up"))
            if victims and n_up > self._min_replicas:
                rid = victims[0][1]
                self._event("scale_down", rid, fleet_size=n_up - 1)
                self._count("scale_downs")
                self.remove_replica(rid)

    # -- rolling param swap -------------------------------------------------
    def refresh_params(self, apply_fn=None, snapshot_dir=None,
                       drain_timeout_s: float = 30.0):
        """Glitch-free rolling swap: for each replica — drain (unroute,
        wait for in-flight zero), apply + repack params, rejoin. Load
        keeps flowing to the other replicas, and because the swapping
        replica is idle, even an injected ``torn_swap`` window is
        unobservable: every response is pure-old or pure-new.
        ``snapshot_dir`` streams weights from a checkpoint store
        instead of the in-process module — the delta-aware path, and
        the only one subprocess/socket replicas accept."""
        for rid in self.replica_ids():
            with self._rlock:
                e = self._entries.get(rid)
                if e is None or e.state != "up":
                    continue
                e.state = "draining"
                self._rebuild_ring()
            self._event("swap_drain", rid)
            try:
                self._await_drain(e, drain_timeout_s)
                e.replica.refresh_params(apply_fn,
                                         snapshot_dir=snapshot_dir)
            finally:
                with self._rlock:
                    if e.state == "draining":
                        e.state = "up"
                        self._rebuild_ring()
            self._event("param_swap", rid)
            self._count("param_swaps")

    # -- introspection ------------------------------------------------------
    def stats(self) -> dict:
        with self._rlock:
            replicas = {
                rid: {"state": e.state, "served": e.served,
                      "failures": e.failures, "in_flight": e.inflight,
                      "breaker": {"state": e.breaker.state,
                                  "trips": e.breaker.trips}}
                for rid, e in self._entries.items()}
            counters = dict(self._counters)
            events = list(self._events)
            lat = sorted(self._lat)
        out = {"replicas": replicas, "counters": counters,
               "events": events}
        if lat:
            out["p50_ms"] = round(lat[len(lat) // 2] * 1e3, 3)
            out["p95_ms"] = round(
                lat[min(len(lat) - 1, int(0.95 * len(lat)))] * 1e3, 3)
        return out

    # -- shutdown ------------------------------------------------------------
    def close(self, drain: bool = True):
        """Stop intake, let in-flight requests finish, stop the
        monitor, close every replica. Idempotent."""
        with self._rlock:
            if self._closed:
                return
            self._closed = True
        self._stop.set()
        self._monitor_thread.join(5.0)
        self._pool.shutdown(wait=True)
        with self._rlock:
            entries = list(self._entries.values())
            self._entries.clear()
            self._ring = []
        for e in entries:
            try:
                e.replica.close()
            except Exception:   # noqa: BLE001 (close the rest anyway)
                _log.exception("replica close failed")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
