"""Reduction of a trace (``xplane.load``'s form) to the numbers the
per-layer readers take: device busy time as a union of intervals, idle
gaps by the host span they fell under, time per operation, convolution
and matmul time, collectives exposed and hidden.

Every device number is taken inside the traced window, which is the
stretch the harness's own host spans (``bench:<name>``, written as
``jax.profiler.TraceAnnotation``) cover on the trace's clock.
"""
import re

SPAN_PREFIX = "bench:"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?$")
# what the MXU does: a bare convolution or dot, or the fusion built
# around one (on the TPU that is the kOutput kind)
_CONV_DOT_TAGS = ("convolution", "dot", "fusion:kOutput", "fusion:kConvolution")


def union(intervals):
    """Sorted, merged copy of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The parts of merged intervals ``a`` that no interval of merged
    ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(intervals, lo, hi):
    return total(clip(intervals, lo, hi))


def host_spans(trace):
    """[(name, start_ns, end_ns)] of the harness's spans, prefix cut."""
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, _, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append((name[len(SPAN_PREFIX):], start, start + dur))
    return sorted(out, key=lambda s: s[1])


def device_lines(trace):
    """{device ordinal: {line name: events}} for the TPU planes."""
    out = {}
    for plane in trace["planes"]:
        m = _DEVICE.match(plane["name"])
        if m:
            out[int(m.group(1))] = {ln["name"]: ln["events"]
                                    for ln in plane["lines"]}
    return out


def _base(name):
    """'fusion.12' -> 'fusion': the instruction's name without its
    number, under which the same operation of every step adds up."""
    return re.sub(r"[.\d]+$", "", name)


def reduce(trace, steps):
    """The numbers of one traced window of ``steps`` whole steps."""
    spans = host_spans(trace)
    devices = device_lines(trace)
    if not spans or not devices:
        raise RuntimeError("the trace holds no %s span or no TPU plane"
                           % SPAN_PREFIX)
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    busy_by_dev = {}
    for dev, lines in devices.items():
        ops = lines.get("XLA Ops", [])
        busy_by_dev[dev] = clip(union([(s, s + d) for _, _, s, d in ops]),
                                lo, hi)
    busy_s = sum(total(b) for b in busy_by_dev.values()) \
        / len(busy_by_dev) / 1e9

    first = min(devices)
    ops = [(n, t, s, d) for n, t, s, d in devices[first].get("XLA Ops", [])
           if s + d > lo and s < hi]
    per_op = {}
    conv_dot = 0.0
    for name, tag, _, dur in ops:
        # XLA names a fusion after what it holds, or just "fusion": then
        # its kind says more
        key = tag if _base(name) == "fusion" else _base(name)
        per_op[key] = per_op.get(key, 0.0) + dur / 1e9
        if tag in _CONV_DOT_TAGS or "convolution" in name:
            conv_dot += dur / 1e9

    # collectives on the first device: a synchronous one is its own
    # event; an asynchronous one runs from its -start to its -done, which
    # the "Async XLA Ops" line draws as one event
    coll = [(s, s + d) for _, tag, s, d in ops if _COLLECTIVE.match(tag)]
    coll += [(s, s + d) for n, tag, s, d
             in devices[first].get("Async XLA Ops", [])
             if _COLLECTIVE.match(tag) and s + d > lo and s < hi]
    coll = clip(union(coll), lo, hi)
    others = union([(s, s + d) for _, tag, s, d in ops
                    if not _COLLECTIVE.match(tag)])
    exposed = subtract(coll, others)

    # idle gaps of the first device, by the innermost host span each fell
    # under (a span's own time is what its nested spans leave of it)
    gaps = subtract([(lo, hi)], busy_by_dev[first])
    idle_by_span = {}
    for i, (name, s, e) in enumerate(spans):
        inner = union([(s2, e2) for j, (_, s2, e2) in enumerate(spans)
                       if j != i and s <= s2 and e2 <= e])
        got = sum(overlap(gaps, a, b) for a, b in subtract([(s, e)], inner))
        if got:
            idle_by_span[name] = idle_by_span.get(name, 0.0) + got / 1e9
    rest = total(gaps) / 1e9 - sum(idle_by_span.values())
    if rest > 1e-9:
        idle_by_span["(no span)"] = rest

    span_s = {}
    for name, s, e in spans:
        span_s[name] = span_s.get(name, 0.0) + (e - s) / 1e9
    return {
        "steps": steps,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "devices": len(devices),
        "per_op_s": per_op,
        "conv_dot_s": conv_dot,
        "collective_s": total(coll) / 1e9,
        "collective_exposed_s": total(exposed) / 1e9,
        "idle_by_span_s": idle_by_span,
        "span_s": span_s,
    }


def breakdown(reduced, top=10):
    """The ledger's ``breakdown``: the device operations that took most
    time and the longest idle gaps by host span."""
    def top_of(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": top_of(reduced["per_op_s"]),
            "idle_gaps": top_of(reduced["idle_by_span_s"])}
