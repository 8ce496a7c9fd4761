"""Profiler trace -> the small form the reduction works on.

``load(dir)`` reads the newest ``*.xplane.pb`` under a
``jax.profiler.start_trace`` directory with nothing but JAX and returns

    {"planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[name, tag, start_ns, dur_ns]]}]}]}

On a device's "XLA Ops" line an event's name is the whole HLO
instruction; ``parse_hlo`` cuts it to the instruction's own name and a
tag, ``<opcode>`` or ``fusion:<kind>``, which is all the reduction reads
(seen on the v5e, PR 23: ``%fusion.7 = (...) fusion(...), kind=kOutput,
calls=...``; a convolution or dot with its epilogue is a ``kOutput``
fusion there).
"""
import glob
import os
import re

_KIND = re.compile(r"kind=(k\w+)")


def parse_hlo(text):
    """('fusion.7', 'fusion:kOutput') from an HLO instruction's text; a
    name that is no instruction comes back as (text, '')."""
    head, sep, rest = text.partition(" = ")
    if not sep or not head.startswith("%"):
        return text, ""
    if rest.startswith("("):            # a tuple type: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.partition(" ")[2]
    opcode = rest.partition("(")[0].strip()
    if opcode == "fusion":
        kind = _KIND.search(rest)
        opcode = "fusion:" + (kind.group(1) if kind else "?")
    return head[1:], opcode


def newest_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise RuntimeError("no .xplane.pb under %s" % trace_dir)
    return files[-1]


def load(trace_dir):
    import jax

    data = jax.profiler.ProfileData.from_file(newest_xplane(trace_dir))
    planes = []
    for plane in data.planes:
        lines = []
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            events = []
            for e in line.events:
                name, tag = parse_hlo(e.name) if device else (e.name, "")
                events.append([name, tag, float(e.start_ns),
                               float(e.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}
