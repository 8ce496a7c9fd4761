"""Device time by the program's named scopes.

In the fused step every op node lowers under ``jax.named_scope("<op>:
<node>")`` inside one of the phases ``fwd`` / ``bwd`` / ``update`` /
``metric`` (``mxnet_tpu/fused_step.py``, ``executor.py``). On the "XLA
Ops" line of a device plane that path is the stat ``tf_op`` of the event's
*metadata* (``XEventMetadata.stats``), which ``jax.profiler.ProfileData``
does not hand out (PERF.md section 7), so ``load`` reads the ``.xplane.pb``
wire format itself: the few fields of ``XSpace`` it needs, nothing else.

``load(path)`` -> ``[(device ordinal, [(name, scope, start_ns, dur_ns)])]``
for the "XLA Ops" line of each ``/device:TPU:<n>`` plane; ``by_part``
sums self time (an event's duration less the events nested in it: a
``while`` encloses its body's) over a window by what ``part_of`` makes of
each scope. A trace whose events carry no scope gives an empty result,
never an error.
"""
import re
import struct

_NODE = re.compile(r"([A-Za-z_][A-Za-z0-9_]*):([A-Za-z0-9_+]+)")
_PHASE = re.compile(r"/(fwd|bwd|update|metric)(/|$)")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def _varint(buf, i):
    val, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf):
    """(field number, wire type, value) of one protobuf message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 1:
            val = struct.unpack_from("<d", buf, i)[0]
            i += 8
        elif wt == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError("wire type %d" % wt)
        yield num, wt, val


def _stat(buf, stat_names):
    """(stat name, value) of one XStat: str_value, or the string a
    ref_value points at."""
    name, value = None, None
    for num, _, val in _fields(buf):
        if num == 1:
            name = stat_names.get(val)
        elif num == 5:
            value = bytes(val).decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(val)
        elif num in (2, 3, 4) and value is None:
            value = val
    return name, value


def _map_entry(buf):
    key, value = None, b""
    for num, _, val in _fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def _plane(buf):
    name, lines, ev_meta, stat_meta = "", [], [], {}
    for num, _, val in _fields(buf):
        if num == 2:
            name = bytes(val).decode()
        elif num == 3:
            lines.append(val)
        elif num == 4:
            ev_meta.append(val)
        elif num == 5:
            key, meta = _map_entry(val)
            for n2, _, v2 in _fields(meta):
                if n2 == 2:
                    stat_meta[key] = bytes(v2).decode("utf-8", "replace")
    return name, lines, ev_meta, stat_meta


def load(path):
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for num, _, plane in _fields(space):
        if num != 1:
            continue
        name, lines, ev_meta, stat_names = _plane(plane)
        m = _DEVICE.match(name)
        if not m:
            continue
        meta = {}                       # id -> (name, scope)
        for entry in ev_meta:
            key, buf = _map_entry(entry)
            ev_name, scope = "", ""
            for n2, _, v2 in _fields(buf):
                if n2 == 2:
                    ev_name = bytes(v2).decode("utf-8", "replace")
                elif n2 == 5:
                    stat, value = _stat(v2, stat_names)
                    if stat == "tf_op" and isinstance(value, str):
                        scope = value
            meta[key] = (ev_name, scope)
        events = []
        for line in lines:
            line_name, t0_ns, evs = "", 0, []
            for n2, _, v2 in _fields(line):
                if n2 == 2:
                    line_name = bytes(v2).decode()
                elif n2 == 3:
                    t0_ns = v2
                elif n2 == 4:
                    evs.append(v2)
            if line_name != "XLA Ops":
                continue
            for ev in evs:
                mid, off_ps, dur_ps = 0, 0, 0
                for n3, _, v3 in _fields(ev):
                    if n3 == 1:
                        mid = v3
                    elif n3 == 2:
                        off_ps = v3
                    elif n3 == 3:
                        dur_ps = v3
                ev_name, scope = meta.get(mid, ("", ""))
                events.append((ev_name, scope, t0_ns + off_ps / 1e3,
                               dur_ps / 1e3))
        out.append((int(m.group(1)), events))
    return sorted(out)


def self_times(events):
    """[(name, scope, self_ns)]: each event's duration less what the events
    nested inside it take."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][2], -events[i][3]))
    own = [e[3] for e in events]
    stack = []                          # indices of open events
    for i in order:
        start, dur = events[i][2], events[i][3]
        while stack and events[stack[-1]][2] + events[stack[-1]][3] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [(events[i][0], events[i][1], max(own[i], 0.0))
            for i in range(len(events))]


def split(scope):
    """(phase, op, node) of a scope path; '' where it names none."""
    phase = _PHASE.search(scope)
    node = _NODE.search(scope)
    return (phase.group(1) if phase else "",
            node.group(1) if node else "", node.group(2) if node else "")


def by_part(events, lo_ns, hi_ns, part_of):
    """Seconds of self time inside [lo, hi) by ``part_of(phase, op, node)``
    (a string), events without a scope under ``"(no scope)"``."""
    inside = [e for e in events if e[2] >= lo_ns and e[2] + e[3] <= hi_ns]
    out = {}
    for _, scope, own in self_times(inside):
        part = part_of(*split(scope)) if scope else "(no scope)"
        out[part] = out.get(part, 0.0) + own / 1e9
    return out


# ---------------------------------------------------------------------------
# what the per-layer readers (``metrics/<name>.py``) share
# ---------------------------------------------------------------------------
def part_ms(trace, parts):
    """Device ms a step under ``parts`` of the reduced trace's ``scope_s``
    (forward, recomputed forward and backward together); ``None`` where
    the trace carries no scopes."""
    by_part = trace.get("scope_s")
    if not by_part:
        return None
    return 1e3 * sum(by_part.get(p, 0.0) for p in parts) / trace["steps"]


def part_roofline(trace, cell, part):
    """``part``'s share of its roofline, %: the least time the chip could
    take for the operations and bytes the reference counts for it
    (``cell["step_parts"]``: three passes, the recomputed forward not among
    them) over the device time under its scopes. Prints which of FLOPs and
    bytes binds. ``None`` where there is nothing to read."""
    took = part_ms(trace, (part,))
    cost = cell.get("step_parts", {}).get(part)
    if not took or not cost:
        return None
    peaks = cell["peaks"]
    t_flops = 1e3 * cost[0] / peaks["flops_per_s"][
        cell["config"]["compute_dtype"]]
    t_bytes = 1e3 * cost[1] / peaks["hbm_bytes_per_s"]
    print("roofline %s: least %.3f ms a step (%s bind: %.3f ms of FLOPs, "
          "%.3f ms of bytes), took %.3f ms" % (
              part, max(t_flops, t_bytes),
              "FLOPs" if t_flops >= t_bytes else "bytes", t_flops, t_bytes,
              took))
    return 100.0 * max(t_flops, t_bytes) / took
