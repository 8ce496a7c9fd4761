"""The program's own host spans, for the per-layer readers that take
them: ``mxnet_tpu.telemetry.spans()``, a ring of ``(name, thread,
start, duration, parent, step)`` on ``time.perf_counter()``, the clock of
the harness's ``spans``. The traced stretch is what those cover:
``[spans[0][1], spans[-1][2]]``.

A program without such spans (one older than the PR that placed them)
gives an empty ring; every reader then finds nothing to read.
"""
from benchmark.trace.reduce import clip, subtract, total, union

SETUP = ("fit.bind", "fit.init_params", "fit.init_optimizer",
         "fit.fused_build")
JAX_TRACE_LOWER = ("jax.trace", "jax.lower")
JAX_BUILD = ("jax.cache_read", "jax.backend_compile")


def ring(metric, since=None):
    """The program's spans, oldest first, or None (and why, on stdout)
    if there are none or the ring has dropped what ``metric`` needs:
    every span that ended after ``since``, or, without ``since``, every
    span since telemetry was reset."""
    from mxnet_tpu import env, telemetry

    entries = telemetry.spans()
    if not entries:
        print("%s: the program recorded no spans" % metric)
        return None
    oldest_end = entries[0][2] + entries[0][3]
    if len(entries) >= env.get("MXNET_TPU_TELEMETRY_SPAN_CAP") \
            and (since is None or oldest_end > since):
        print("%s: the span ring (MXNET_TPU_TELEMETRY_SPAN_CAP) has "
              "dropped entries it needs: its oldest ended at %.3f"
              % (metric, oldest_end))
        return None
    return entries


def stretch(spans):
    """(start, end) of the traced stretch, from the harness's spans."""
    return spans[0][1], spans[-1][2]


def covered(entries, names, lo=float("-inf"), hi=float("inf"), less=()):
    """Seconds of [lo, hi] that spans named in ``names`` cover, thread by
    thread, each instant once however the spans nest, less what spans
    named in ``less`` cover of it."""
    keep, drop = {}, {}
    for name, tid, start, dur, _parent, _step in entries:
        if name in names:
            keep.setdefault(tid, []).append((start, start + dur))
        elif name in less:
            drop.setdefault(tid, []).append((start, start + dur))
    return sum(total(subtract(clip(union(spans), lo, hi),
                              union(drop.get(tid, []))))
               for tid, spans in keep.items())


def before_stretch(metric, spans):
    """The program's spans that ended before the traced stretch began
    (set-up's), or None as ``ring`` gives it."""
    entries = ring(metric)
    if entries is None:
        return None
    lo = stretch(spans)[0]
    return [e for e in entries if e[2] + e[3] <= lo]


def whole_steps_ms(entries, lo, hi):
    """Durations (ms) of the ``fit.step`` spans wholly inside [lo, hi]."""
    return [dur * 1e3 for name, _tid, start, dur, _parent, _step in entries
            if name == "fit.step" and start >= lo and start + dur <= hi]
