"""Unified telemetry: framework-wide counters, gauges, histograms and
host-side spans.

The reference's only observability was the ``Monitor`` callback,
``Speedometer`` and per-op engine logging (SURVEY §5); ``profiler.py``
added the XLA device trace. Neither instruments the layers where
regressions actually hide — engine dispatch, the input pipeline, kvstore
traffic, JIT recompilation. This module is the process-global metric
registry those layers report through:

* **Counters** — monotonically increasing ints (``engine.push``,
  ``io.batches``, ``kvstore.push_bytes``).
* **Gauges** — last-write-wins floats (``train.samples_per_sec``).
* **Histograms** — bounded: running count/sum/min/max plus a fixed-size
  reservoir of recent samples for percentiles. Memory is O(capacity)
  no matter how long the job runs.
* **Spans** — host-side wall-time intervals (``with telemetry.span(n)``)
  kept in a bounded ring with their thread, enclosing span and step, and
  written as ``TraceAnnotation("mx:<n>")`` so that a profiler capture
  (``jax.profiler.start_trace`` or ``mx.profiler.start``) shows host
  work on the same timeline as the device ops.

Overhead contract: telemetry is DISABLED by default; every recording
helper starts with one module-level flag check and returns immediately,
taking no locks and allocating nothing. Enable with
``MXNET_TPU_TELEMETRY=1`` or :func:`enable`. The write path when enabled
takes one small per-metric lock (increments from engine worker threads
must not lose updates); the disabled path takes none.

Exporters::

    telemetry.snapshot()            # nested dict, one leaf per metric
    telemetry.dump_jsonl(path)      # append ONE step record (crash-safe)
    telemetry.write_chrome_trace(p) # host spans -> Perfetto-loadable json

See docs/performance.md ("Telemetry") for the metric name table and the
JSONL schema.
"""
from __future__ import annotations

import bisect
import contextlib
import copy
import json
import os
import threading
import time
from collections import deque
from typing import Dict, Optional, Sequence

from . import env as _env
from .base import MXNetError

__all__ = ["enabled", "enable", "disable", "counter", "gauge", "histogram",
           "inc", "set_gauge", "observe", "span", "spans", "next_step",
           "snapshot", "reset",
           "dump_jsonl", "write_chrome_trace", "Counter", "Gauge",
           "Histogram", "peek", "metrics_items", "merge_snapshots",
           "bucket_quantile", "sample_quantile", "DEFAULT_BUCKET_BOUNDS"]

_ENABLED = _env.get("MXNET_TPU_TELEMETRY")

_reg_lock = threading.Lock()
_metrics: Dict[str, object] = {}

# span ring: bounded so a never-exported long run cannot grow host memory
_SPAN_CAP = _env.get("MXNET_TPU_TELEMETRY_SPAN_CAP")
_spans: deque = deque(maxlen=_SPAN_CAP)
# perf_counter -> wall-clock offset, fixed at import so span timestamps
# from every thread share one epoch (and can be laid next to an XLA
# trace, which stamps wall time)
_EPOCH = time.time() - time.perf_counter()

_step_lock = threading.Lock()
_step = 0

# the step number ring entries carry: what the spans of one training
# step share. Bumped by next_step() (the fit loop, once an iteration);
# set-up's spans carry 0
_span_step = 0
_tls = threading.local()      # per-thread stack of open spans
# what spans need of JAX, resolved once by _hook_jax() on the way to
# being enabled (importing this module must not import jax.profiler)
_TraceAnnotation = None
_outermost_trace = None

# JAX's own duration events -> the span each is recorded as (the event
# arrives when the work ends, so the span is laid back from "now")
_JAX_EVENT_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_read",
}


# JAX's plain events about its persistent cache -> what a build that saw
# one reads as its ``cache``: a hit is counted when the cache answers, a
# miss when JAX WRITES what XLA built (a build that saw neither ran with
# no cache directory, or under JAX's thresholds of a second and a size)
_JAX_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_misses": "built",
    "/jax/compilation_cache/cache_hits": "read",
}


def enabled() -> bool:
    return _ENABLED


def _on_jax_duration(event, duration_secs, **_kw):
    name = _JAX_EVENT_SPANS.get(event)
    # a trace that ends inside another (the jitted helpers a traced
    # function calls, thousands in one train step) is left out: the
    # enclosing jax.trace covers its time, and the ring is bounded
    if name is None or not (_outermost_trace is None
                            or _outermost_trace()):
        return
    build = getattr(_tls, "build", None)
    if build is not None:
        build[name] += duration_secs
    if _ENABLED:
        stack = getattr(_tls, "stack", None)
        _spans.append((name, threading.get_ident(),
                       time.perf_counter() - duration_secs, duration_secs,
                       stack[-1].name if stack else None, _span_step))


def _on_jax_event(event, **_kw):
    answer = _JAX_CACHE_EVENTS.get(event)
    build = getattr(_tls, "build", None)
    if answer is not None and build is not None:
        build["cache"] = answer


@contextlib.contextmanager
def jax_build():
    """What JAX reports ON THIS THREAD until the block ends, as one dict
    a build: the seconds of its four duration events under their span
    names (``jax.trace``, ``jax.lower``, ``jax.cache_read``,
    ``jax.backend_compile``; outermost traces only, and the last ENCLOSES
    the cache read) and ``cache``: ``"read"`` if the persistent cache
    answered, ``"built"`` if XLA built the program and the cache took it,
    ``"off"`` if the cache had no part (no directory; a program under
    JAX's thresholds). Collected whether telemetry is on or not; a build
    opened inside another takes its events alone."""
    _hook_jax()
    outer = getattr(_tls, "build", None)
    build = _tls.build = dict.fromkeys(_JAX_EVENT_SPANS.values(), 0.0)
    build["cache"] = "off"
    try:
        yield build
    finally:
        _tls.build = outer


def _hook_jax():
    """Once per process: JAX's ``TraceAnnotation`` for the spans, and the
    listeners through which every program JAX traces, lowers, builds or
    reads from its persistent cache while telemetry is on leaves a
    ``jax.*`` span, whichever call site asked for it, and an open
    :func:`jax_build` its seconds and the cache's answer."""
    global _TraceAnnotation, _outermost_trace
    with _reg_lock:
        if _TraceAnnotation is not None:
            return
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    from jax import monitoring
    try:
        from jax._src import core as _jax_core
        _outermost_trace = _jax_core.trace_state_clean
    except (ImportError, AttributeError):
        pass                 # a JAX without it: every trace is recorded
    monitoring.register_event_duration_secs_listener(_on_jax_duration)
    monitoring.register_event_listener(_on_jax_event)


def enable():
    global _ENABLED
    _hook_jax()
    _ENABLED = True


if _ENABLED:
    _hook_jax()


def disable():
    global _ENABLED
    _ENABLED = False


class Counter:
    """Monotonic counter; thread-safe increments."""

    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: int = 1):
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value

    def export(self):
        return self._value


class Gauge:
    """Last-write-wins float."""

    __slots__ = ("name", "_value")

    def __init__(self, name: str):
        self.name = name
        self._value = 0.0

    def set(self, v: float):
        self._value = float(v)

    @property
    def value(self) -> float:
        return self._value

    def export(self):
        return self._value


# Default latency-oriented bucket ladder (milliseconds). Finite upper
# bounds only; the implicit +Inf bucket count is the histogram's total
# count, so JSON exports never need an "Infinity" literal.
DEFAULT_BUCKET_BOUNDS = (0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
                         250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0)


class Histogram:
    """Bounded histogram: exact count/sum/min/max, fixed cumulative
    buckets (Prometheus ``le`` semantics, exact forever), plus a ring of
    the most recent ``capacity`` samples for percentile estimates."""

    __slots__ = ("name", "capacity", "bounds", "_lock", "_count", "_sum",
                 "_min", "_max", "_ring", "_idx", "_bucket_counts")

    def __init__(self, name: str, capacity: int = 512,
                 bounds: Optional[Sequence[float]] = None):
        self.name = name
        self.capacity = int(capacity)
        self.bounds = tuple(sorted(float(b) for b in
                                   (DEFAULT_BUCKET_BOUNDS if bounds is None
                                    else bounds)))
        self._lock = threading.Lock()
        self._count = 0
        self._sum = 0.0
        self._min = None
        self._max = None
        self._ring = []
        self._idx = 0
        # per-bucket (non-cumulative) counts; index len(bounds) = overflow
        self._bucket_counts = [0] * (len(self.bounds) + 1)

    def observe(self, v: float):
        v = float(v)
        with self._lock:
            self._count += 1
            self._sum += v
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v
            self._bucket_counts[bisect.bisect_left(self.bounds, v)] += 1
            if len(self._ring) < self.capacity:
                self._ring.append(v)
            else:
                self._ring[self._idx] = v
                self._idx = (self._idx + 1) % self.capacity

    @property
    def count(self) -> int:
        return self._count

    def export(self, include_sample: bool = False) -> dict:
        """Summary dict. ``buckets`` carries cumulative counts per finite
        ``le`` bound (the +Inf count is ``count``); with
        ``include_sample`` the sorted sample ring rides along so a
        federator can merge exact percentiles instead of interpolating
        from buckets."""
        with self._lock:
            n, s = self._count, self._sum
            lo, hi = self._min, self._max
            sample = sorted(self._ring)
            per_bucket = list(self._bucket_counts)
        cum, acc = [], 0
        for c in per_bucket[:-1]:
            acc += c
            cum.append(acc)
        buckets = {"bounds": list(self.bounds), "counts": cum}
        if n == 0:
            return {"count": 0, "buckets": buckets}
        m = len(sample)
        out = {
            "count": n,
            "sum": s,
            "mean": s / n,
            "min": lo,
            "max": hi,
            "p50": sample[m // 2],
            "p90": sample[min(m - 1, int(m * 0.9))],
            "p99": sample[min(m - 1, int(m * 0.99))],
            "buckets": buckets,
        }
        if include_sample:
            out["sample"] = sample
        return out


def _get(name: str, cls, **kw):
    m = _metrics.get(name)
    if m is None:
        with _reg_lock:
            m = _metrics.get(name)
            if m is None:
                m = cls(name, **kw)
                _metrics[name] = m
    if not isinstance(m, cls):
        raise MXNetError("telemetry metric %r is a %s, not a %s"
                         % (name, type(m).__name__, cls.__name__))
    return m


def counter(name: str) -> Counter:
    return _get(name, Counter)


def gauge(name: str) -> Gauge:
    return _get(name, Gauge)


def histogram(name: str, capacity: int = 512,
              bounds: Optional[Sequence[float]] = None) -> Histogram:
    return _get(name, Histogram, capacity=capacity, bounds=bounds)


def peek(name: str, kind: str = "counter"):
    """Read a metric's current raw value WITHOUT registering it: a
    counter/gauge value, or a histogram's running sum when
    ``kind="hist_sum"``. Returns None for an unregistered name. This is
    the step-trace delta reader — it must not materialize metrics the
    instrumented layers never touched."""
    m = _metrics.get(name)
    if m is None:
        return None
    if isinstance(m, Histogram):
        return m._sum if kind == "hist_sum" else m._count
    return m._value


def metrics_items():
    """Sorted (name, metric) pairs — the exposition-format reader."""
    with _reg_lock:
        return sorted(_metrics.items())


# -- recording fast path (one flag check, immediate return when off) ----
def inc(name: str, n: int = 1):
    if not _ENABLED:
        return
    counter(name).inc(n)


def set_gauge(name: str, v: float):
    if not _ENABLED:
        return
    gauge(name).set(v)


def observe(name: str, v: float):
    if not _ENABLED:
        return
    histogram(name).observe(v)


# -- spans ---------------------------------------------------------------
class _NoSpan:
    """What :func:`span` hands out while telemetry is off: one shared
    object whose every method does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def cancel(self):
        pass

    def elapsed_ms(self) -> float:
        return 0.0


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("name", "t0", "parent", "step", "_ann", "_keep")

    def __init__(self, name: str):
        self.name = name
        self._keep = True

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self.parent = stack[-1].name if stack else None
        self.step = _span_step
        stack.append(self)
        # outside a profiler capture this is a no-op of the runtime's
        self._ann = _TraceAnnotation("mx:" + self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self.t0
        self._ann.__exit__(None, None, None)
        _tls.stack.pop()
        if self._keep:
            _spans.append((self.name, threading.get_ident(), self.t0, dur,
                           self.parent, self.step))
            observe("span.%s_ms" % self.name, dur * 1e3)
        return False

    def cancel(self):
        """The interval turned out not to be one (the fit loop's
        ``fit.step`` whose ``next()`` found the epoch over): leave no
        ring entry and no histogram sample."""
        self._keep = False

    def elapsed_ms(self) -> float:
        return (time.perf_counter() - self.t0) * 1e3


def span(name: str):
    """Host-side named interval, ``with telemetry.span(name):``. Recorded
    into the bounded span ring and the ``span.<name>_ms`` histogram, and
    written as ``TraceAnnotation("mx:<name>")``, so that any profiler
    capture shows it beside the device's work. Off, it returns one
    shared object that does nothing."""
    if not _ENABLED:
        return _NO_SPAN
    return _Span(name)


def next_step():
    """A new training step begins: spans opened from here on carry the
    next step number."""
    global _span_step
    if _ENABLED:
        _span_step += 1


def spans():
    """The buffered ``(name, tid, start, duration_s, parent, step)``
    tuples, oldest first: start on ``time.perf_counter()``, ``parent``
    the name of the span that enclosed this one on its thread (or
    None), ``step`` the number :func:`next_step` had reached."""
    return list(_spans)


def write_chrome_trace(path: str, extra_events: Optional[list] = None):
    """Write buffered host spans in the chrome trace event format.
    Timestamps are wall-clock microseconds, the same clock domain the
    XLA trace stamps, so both load side by side in Perfetto.

    ``process_name``/``thread_name`` metadata events (ph="M") name
    this process's lanes, so a multi-process merged trace reads as
    named lanes instead of bare pids/tids. ``extra_events`` appends
    pre-built chrome events verbatim — the distributed tracer
    (:mod:`mxnet_tpu.dtrace`) reuses this writer for its merged
    cross-process span trees."""
    import sys

    spans = list(_spans)
    pid = os.getpid()
    thread_names = {t.ident: t.name for t in threading.enumerate()}
    meta = [{"name": "process_name", "ph": "M", "pid": pid,
             "args": {"name": "%s (pid %d)"
                      % (os.path.basename(sys.argv[0] or "python"),
                         pid)}}]
    for tid in sorted({sp[1] for sp in spans}):
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid,
                     "args": {"name": thread_names.get(
                         tid, "tid-%d" % tid)}})
    events = meta + [
        {"name": name, "ph": "X", "cat": "host",
         "pid": pid, "tid": tid,
         "ts": (t0 + _EPOCH) * 1e6, "dur": dur * 1e6}
        for name, tid, t0, dur, _parent, _step_no in spans]
    if extra_events:
        events.extend(extra_events)
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return len(events)


# -- exporters -----------------------------------------------------------
def snapshot() -> dict:
    """All metrics as a nested dict keyed by the dot-split name
    (``engine.push`` -> ``{"engine": {"push": N}}``). Counters export
    ints, gauges floats, histograms summary dicts. A name that is both
    a leaf and a prefix keeps its leaf value under ``"_value"``."""
    with _reg_lock:
        items = sorted(_metrics.items())
    out: dict = {}
    for name, m in items:
        parts = name.split(".")
        node = out
        for p in parts[:-1]:
            nxt = node.get(p)
            if not isinstance(nxt, dict):
                nxt = {} if nxt is None else {"_value": nxt}
                node[p] = nxt
            node = nxt
        leaf = parts[-1]
        if isinstance(node.get(leaf), dict):
            node[leaf]["_value"] = m.export()
        else:
            node[leaf] = m.export()
    return out


# -- federation primitives -----------------------------------------------
def sample_quantile(sample: Sequence[float], q: float) -> Optional[float]:
    """Quantile of a pre-sorted sample, using the same nearest-rank
    convention as :meth:`Histogram.export` (``sample[int(m*q)]``,
    clamped). Returns None for an empty sample."""
    m = len(sample)
    if m == 0:
        return None
    if q == 0.5:
        return sample[m // 2]
    return sample[min(m - 1, int(m * q))]


def bucket_quantile(buckets: dict, count: int, q: float,
                    hi: Optional[float] = None) -> Optional[float]:
    """Quantile interpolated from a cumulative-bucket export
    (``{"bounds": [...], "counts": [...]}``). Linear within the bucket
    holding the target rank; ranks past the last finite bound clamp to
    ``hi`` (observed max) or the last bound. Returns None when empty."""
    if count <= 0 or not buckets:
        return None
    bounds = buckets.get("bounds") or []
    counts = buckets.get("counts") or []
    target = q * count
    prev_bound, prev_cum = 0.0, 0
    for bound, cum in zip(bounds, counts):
        if cum >= target:
            in_bucket = cum - prev_cum
            if in_bucket <= 0:
                return bound
            frac = (target - prev_cum) / in_bucket
            return prev_bound + (bound - prev_bound) * min(1.0, frac)
        prev_bound, prev_cum = bound, cum
    if hi is not None:
        return float(hi)
    return float(bounds[-1]) if bounds else None


def _is_hist_export(d) -> bool:
    return isinstance(d, dict) and "count" in d and "buckets" in d


_MERGE_SAMPLE_CAP = 4096


def _merge_hist(a: dict, b: dict) -> dict:
    ba, bb = a.get("buckets") or {}, b.get("buckets") or {}
    bounds_a = list(ba.get("bounds") or [])
    bounds_b = list(bb.get("bounds") or [])
    if bounds_a and bounds_b and bounds_a != bounds_b:
        raise MXNetError(
            "merge_snapshots: conflicting histogram bucket bounds "
            "%r vs %r — federation requires one ladder per metric"
            % (bounds_a, bounds_b))
    n = int(a.get("count", 0)) + int(b.get("count", 0))
    bounds = bounds_a or bounds_b
    counts_a = list(ba.get("counts") or [0] * len(bounds))
    counts_b = list(bb.get("counts") or [0] * len(bounds))
    counts = [x + y for x, y in zip(counts_a, counts_b)]
    out = {"count": n, "buckets": {"bounds": bounds, "counts": counts}}
    if n == 0:
        return out
    out["sum"] = float(a.get("sum", 0.0)) + float(b.get("sum", 0.0))
    out["mean"] = out["sum"] / n
    mins = [v for v in (a.get("min"), b.get("min")) if v is not None]
    maxs = [v for v in (a.get("max"), b.get("max")) if v is not None]
    if mins:
        out["min"] = min(mins)
    if maxs:
        out["max"] = max(maxs)
    sample = sorted((a.get("sample") or []) + (b.get("sample") or []))
    if len(sample) > _MERGE_SAMPLE_CAP:
        # decimate evenly rather than truncate: keeps the distribution
        step = len(sample) / float(_MERGE_SAMPLE_CAP)
        sample = [sample[int(i * step)] for i in range(_MERGE_SAMPLE_CAP)]
    if sample:
        out["sample"] = sample
        for q, key in ((0.5, "p50"), (0.9, "p90"), (0.99, "p99")):
            out[key] = sample_quantile(sample, q)
    else:
        for q, key in ((0.5, "p50"), (0.9, "p90"), (0.99, "p99")):
            v = bucket_quantile(out["buckets"], n, q, hi=out.get("max"))
            if v is not None:
                out[key] = v
    return out


def _merge_into(dst: dict, src: dict, path: str):
    for k, v in src.items():
        here = "%s.%s" % (path, k) if path else k
        if k not in dst:
            dst[k] = copy.deepcopy(v)
            continue
        cur = dst[k]
        if _is_hist_export(cur) and _is_hist_export(v):
            dst[k] = _merge_hist(cur, v)
        elif _is_hist_export(cur) or _is_hist_export(v):
            raise MXNetError("merge_snapshots: %r is a histogram in one "
                             "snapshot and not in another" % here)
        elif isinstance(cur, dict) and isinstance(v, dict):
            _merge_into(cur, v, here)
        elif isinstance(cur, (int, float)) and isinstance(v, (int, float)):
            # counters (ints) and gauges (floats) both merge by sum; a
            # federator wanting per-source gauge fan-out keeps the
            # original snapshots alongside the merged view
            dst[k] = cur + v
        else:
            raise MXNetError("merge_snapshots: %r has mismatched kinds "
                             "(%s vs %s)" % (here, type(cur).__name__,
                                             type(v).__name__))


def merge_snapshots(snaps: Sequence[dict]) -> dict:
    """Merge N :func:`snapshot`-shaped nested dicts into one fleet
    rollup: counters and gauges sum, histogram exports merge bucket-wise
    (counts/sums add, min/max combine, samples concatenate, percentiles
    recomputed — exact from merged samples when every input carried one,
    bucket-interpolated otherwise). Histograms with conflicting bucket
    ladders raise :class:`MXNetError` rather than silently misbinning.
    Inputs are never mutated."""
    out: dict = {}
    for s in snaps:
        if s:
            _merge_into(out, s, "")
    return out


def dump_jsonl(path: str, extra: Optional[dict] = None) -> dict:
    """Append ONE step record (timestamp, step index, full snapshot) to
    ``path``. Crash-safe: the whole line goes out in a single
    ``os.write`` on an ``O_APPEND`` fd — POSIX appends of one write are
    atomic with respect to other appenders, so a crash (or a concurrent
    writer) can interleave or truncate at worst the final line, never
    the middle of an earlier record the flight recorder will read back.
    ``MXNET_TPU_TELEMETRY_FSYNC=1`` adds an fsync per record for
    machines where losing the last buffered lines to a power cut
    matters more than the syscall cost."""
    global _step
    with _step_lock:
        _step += 1
        step = _step
    rec = {"ts": round(time.time(), 6), "step": step,
           "telemetry": snapshot()}
    if extra:
        rec.update(extra)
    line = (json.dumps(rec) + "\n").encode("utf-8")
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
    try:
        os.write(fd, line)
        if _env.get("MXNET_TPU_TELEMETRY_FSYNC"):
            os.fsync(fd)
    finally:
        os.close(fd)
    return rec


def reset():
    """Clear every metric, span, and the step counter (test and
    benchmark-harness isolation). The enabled flag is left as-is."""
    global _step, _span_step
    with _reg_lock:
        _metrics.clear()
    _spans.clear()
    _span_step = 0
    with _step_lock:
        _step = 0
