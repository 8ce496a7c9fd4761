#!/usr/bin/env python
"""Render a step-trace JSONL (StepTrace.dump_jsonl / telemetry
dump_jsonl) or a flight-recorder crash-dump directory into a
human-readable table: the top-k slowest steps with their dominant
delta, plus any anomaly events and crash metadata.

Usage::

    python tools/trace_report.py RUN.jsonl [--top K]
    python tools/trace_report.py /tmp/mxnet_tpu_crash/flight-...-pid123-1
    python tools/trace_report.py --view waterfall <trace_id>

Stdlib only — runs on any box the crash dump was copied to.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

DELTA_COLS = ("io_stall_ms", "prefetch_stall_ms", "h2d_bytes",
              "kv_push_bytes", "kv_pull_bytes", "recompiles",
              "dispatches", "fused_recompiles", "fallbacks",
              "sanitizer_trips")


def load_records(path):
    """Step records from a JSONL file. Accepts both the StepTrace
    schema (latency_ms + deltas) and telemetry.dump_jsonl records
    (step_ms, no deltas); skips unparseable lines (a crash may truncate
    the final one)."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if "latency_ms" not in rec and "step_ms" in rec:
                rec = dict(rec, latency_ms=rec["step_ms"])
            if "latency_ms" in rec:
                records.append(rec)
    return records


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return "%.0f%s" % (n, unit) if unit == "B" \
                else "%.1f%s" % (n, unit)
        n /= 1024.0


def render(records, top=10):
    """Top-``top`` slowest steps as an aligned text table."""
    if not records:
        return "no step records\n"
    slowest = sorted(records, key=lambda r: -r.get("latency_ms", 0.0))[:top]
    lats = sorted(r["latency_ms"] for r in records)
    header = ("step", "latency_ms", "dominant", "io_stall_ms",
              "prefetch_ms", "h2d", "kv_push", "kv_pull", "recompiles",
              "dispatch", "fused_rc", "fallbacks", "san_trips")
    rows = [header]
    for r in slowest:
        d = r.get("deltas", {})
        rows.append((
            str(r.get("step", "?")),
            "%.2f" % r["latency_ms"],
            str(r.get("dominant", "-")),
            "%.2f" % d.get("io_stall_ms", 0.0),
            "%.2f" % d.get("prefetch_stall_ms", 0.0),
            _fmt_bytes(d.get("h2d_bytes", 0)),
            _fmt_bytes(d.get("kv_push_bytes", 0)),
            _fmt_bytes(d.get("kv_pull_bytes", 0)),
            str(d.get("recompiles", 0)),
            str(d.get("dispatches", 0)),
            str(d.get("fused_recompiles", 0)),
            str(d.get("fallbacks", 0)),
            str(d.get("sanitizer_trips", 0)),
        ))
    widths = [max(len(row[i]) for row in rows) for i in range(len(header))]
    out = ["%d steps, latency p50=%.2fms max=%.2fms; top %d slowest:"
           % (len(records), lats[len(lats) // 2], lats[-1], len(slowest)),
           ""]
    for j, row in enumerate(rows):
        out.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
        if j == 0:
            out.append("  ".join("-" * w for w in widths))
    return "\n".join(out) + "\n"


def render_events(events):
    if not events:
        return ""
    out = ["", "%d anomaly events:" % len(events)]
    for ev in events:
        detail = ", ".join("%s=%s" % (k, v) for k, v in sorted(ev.items())
                           if k not in ("type", "step", "ts"))
        out.append("  step %-6s %-12s %s"
                   % (ev.get("step", "?"), ev.get("type", "?"), detail))
    return "\n".join(out) + "\n"


def _hist_rows(node, prefix=""):
    """Flatten telemetry snapshot subtree into (name, summary) pairs.
    A name that is both leaf and prefix keeps its own summary under
    ``_value`` (see telemetry.snapshot)."""
    rows = []
    if not isinstance(node, dict):
        return rows
    if "count" in node and not isinstance(node.get("count"), dict):
        return [(prefix or "(all)", node)]
    for k, v in sorted(node.items()):
        name = prefix if k == "_value" else \
            ("%s.%s" % (prefix, k) if prefix else k)
        if k == "_value":
            rows.extend(_hist_rows(v, name or "(all)"))
        else:
            rows.extend(_hist_rows(v, name))
    return rows


def render_locks(telemetry):
    """Lock-contention (``lock.wait_ms`` histograms, fed by the
    `locks` sanitizer's instrumented locks) and ``sanitizer.trips``
    counters from a telemetry snapshot."""
    out = []
    wait = telemetry.get("lock", {}).get("wait_ms")
    rows = [(n, s) for n, s in _hist_rows(wait)
            if s.get("count", 0) > 0]
    if rows:
        out.append("lock contention (lock.wait_ms):")
        header = ("lock", "acquires", "mean_ms", "p50_ms", "p90_ms",
                  "max_ms")
        table = [header]
        for name, s in rows:
            table.append((name, str(s["count"]), "%.3f" % s["mean"],
                          "%.3f" % s["p50"], "%.3f" % s["p90"],
                          "%.3f" % s["max"]))
        widths = [max(len(r[i]) for r in table)
                  for i in range(len(header))]
        for j, r in enumerate(table):
            out.append("  " + "  ".join(c.rjust(w)
                                        for c, w in zip(r, widths)))
            if j == 0:
                out.append("  " + "  ".join("-" * w for w in widths))
    trips = telemetry.get("sanitizer", {}).get("trips")
    if trips is not None:
        if isinstance(trips, dict):
            total = trips.get("_value", 0)
            detail = ", ".join("%s=%s" % (k, v)
                               for k, v in sorted(trips.items())
                               if k != "_value")
            out.append("sanitizer trips: %s%s"
                       % (total, " (%s)" % detail if detail else ""))
        elif trips:
            out.append("sanitizer trips: %s" % trips)
    return "\n".join(out) + "\n" if out else ""


def render_ckpt(telemetry):
    """Preemption-safety counters (``ckpt.*``, fed by
    mxnet_tpu/checkpoint.py) from a telemetry snapshot: snapshot
    saves/bytes/latency, restores, SIGTERM grace saves, and torn files
    skipped at load."""
    ck = telemetry.get("ckpt")
    if not isinstance(ck, dict):
        return ""

    def _n(key):
        v = ck.get(key, 0)
        if isinstance(v, dict):
            v = v.get("_value", 0)
        return v

    counters = ("saves", "bytes", "restores", "preempt_saves",
                "preempt_abandoned", "torn_skipped")
    vals = {k: _n(k) for k in counters}
    if not any(vals.values()):
        return ""
    out = ["checkpoint (ckpt.*):",
           "  " + "  ".join("%s=%s" % (k, vals[k]) for k in counters)]
    rows = [(n, s) for n, s in _hist_rows(ck.get("save_ms"))
            if s.get("count", 0) > 0]
    for _, s in rows:
        out.append("  save_ms: mean=%.1f  p50=%.1f  p90=%.1f  max=%.1f"
                   % (s["mean"], s["p50"], s["p90"], s["max"]))
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# xprof views (compile / ops / memory) over record files
# ---------------------------------------------------------------------------

def load_bench_records(path):
    """Dict records from a record file: one JSON object per line (any
    dict line is kept, unparseable lines skipped), or one pretty-printed
    object or list. The views `bench`, `serve`, `fleet` and `wire` read
    records no tool writes since PR 28 (ROADMAP Design 4); tests feed
    the renderers dicts."""
    recs = []
    with open(path) as f:
        body = f.read()
    try:
        whole = json.loads(body)
    except ValueError:
        pass
    else:
        if isinstance(whole, dict):
            return [whole]
        if isinstance(whole, list):
            return [r for r in whole if isinstance(r, dict)]
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                r = json.loads(line)
            except ValueError:
                continue
            if isinstance(r, dict):
                recs.append(r)
    return recs


def latest_xprof_record(recs):
    """The newest record carrying an xprof compile-registry summary."""
    for r in reversed(recs):
        if isinstance(r.get("xprof"), dict):
            return r
    return None


def _main_site(xp):
    """(site_name, site_summary) of the executable that owns the step:
    bench.train_step when present, else the site with the most FLOPs."""
    sites = xp.get("sites") or {}
    if "bench.train_step" in sites:
        return "bench.train_step", sites["bench.train_step"]
    best = None
    for name, s in sorted(sites.items()):
        fl = ((s.get("last") or {}).get("flops")) or 0
        if best is None or fl > best[2]:
            best = (name, s, fl)
    return (best[0], best[1]) if best else (None, {})


def _table(rows):
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    out = []
    for j, r in enumerate(rows):
        out.append("  " + "  ".join(c.rjust(w) for c, w in zip(r, widths)))
        if j == 0:
            out.append("  " + "  ".join("-" * w for w in widths))
    return out


def render_bench_summary(rec):
    """The one-line "analytic vs measured MFU, gap attributed to
    <category>" headline for the top of the bench report."""
    xp = rec.get("xprof") or {}
    ana = xp.get("bench_analysis") or {}
    measured = rec.get("mfu_pct")
    analytic = rec.get("analytic_mfu", ana.get("analytic_mfu_pct"))
    _site, s = _main_site(xp)
    bd = ((s.get("last") or {}).get("op_breakdown")) or {}
    bound = ana.get("bound", "unknown")
    # blame the category that owns the executable: the biggest
    # byte-mover when bandwidth-bound, else the biggest FLOP owner
    key = "bytes" if bound == "bandwidth" else "flops"
    total_fl = sum(v.get("flops", 0) for v in bd.values()) or 1
    cat = max(bd, key=lambda c: bd[c].get(key, 0)) if bd else None
    blame = "unattributed (no op breakdown)"
    if cat:
        blame = "%s (%.0f%% of FLOPs, %s-bound)" % (
            cat, 100.0 * bd[cat].get("flops", 0) / total_fl,
            bound if bound != "unknown" else "unknown")
    fmt = lambda v: "%.1f%%" % v if v is not None else "n/a"  # noqa: E731
    gap = ("%.1fpt" % abs(analytic - measured)
           if analytic is not None and measured is not None else "n/a")
    out = ("analytic MFU %s vs measured %s — gap %s, attributed to %s\n"
           % (fmt(analytic), fmt(measured), gap, blame))
    coll = collective_fraction(rec)
    if coll is not None:
        out += ("collective (gradient exchange): %.1f%% of FLOPs, "
                "%.1f%% of bytes moved\n"
                % (100.0 * coll["flop_fraction"],
                   100.0 * coll["byte_fraction"]))
        out += _render_collective_axes(coll)
    return out


# which mesh axis each collective opcode serves: the param
# gather/scatter legs are the fsdp (ZeRO) exchange, the mean-psum
# all-reduce is the dp exchange. -start/-done variants fold onto their
# base opcode.
_AXIS_OPS = (("fsdp", ("all-gather", "reduce-scatter")),
             ("dp", ("all-reduce",)))


def _render_collective_axes(coll):
    """Per-axis breakdown of the collective bytes (``by_op`` sub-
    buckets from the HLO breakdown): 'fsdp: ... via all-gather+
    reduce-scatter / dp: ... via all-reduce'. Empty string when the
    record predates by_op."""
    by_op = coll.get("by_op") or {}
    if not by_op:
        return ""
    total = sum(v.get("bytes", 0) for v in by_op.values()) or 1

    def base(op):
        return op[:-6] if op.endswith("-start") else (
            op[:-5] if op.endswith("-done") else op)

    lines = []
    seen = set()
    for axis, ops in _AXIS_OPS:
        byts = ops_n = 0
        used = []
        for op, v in by_op.items():
            if base(op) in ops:
                seen.add(op)
                byts += v.get("bytes", 0)
                ops_n += v.get("count", 0)
                used.append(base(op))
        if ops_n:
            lines.append("  %s axis: %.1f%% of collective bytes "
                         "(%d op%s: %s)"
                         % (axis, 100.0 * byts / total, ops_n,
                            "s" if ops_n != 1 else "",
                            "+".join(sorted(set(used)))))
    other = {op: v for op, v in by_op.items() if op not in seen}
    if other:
        byts = sum(v.get("bytes", 0) for v in other.values())
        ops_n = sum(v.get("count", 0) for v in other.values())
        lines.append("  other: %.1f%% of collective bytes (%d ops: %s)"
                     % (100.0 * byts / total, ops_n,
                        "+".join(sorted(other))))
    return "\n".join(lines) + "\n" if lines else ""


def collective_fraction(rec):
    """Fraction of the main executable's FLOPs/bytes in the
    ``collective`` HLO category (all-reduce/all-gather/...): the cost of
    the sharded fused step's in-jit gradient exchange. None when no op
    breakdown (or no collective ops) was recorded."""
    xp = rec.get("xprof") or {}
    _site, s = _main_site(xp)
    bd = ((s.get("last") or {}).get("op_breakdown")) or {}
    if not bd or "collective" not in bd:
        # multichip records carry the precomputed fraction directly
        c = rec.get("collective")
        if isinstance(c, dict) and "byte_fraction" in c:
            return {"flop_fraction": c.get("flop_fraction", 0.0),
                    "byte_fraction": c.get("byte_fraction", 0.0),
                    "ops": c.get("ops", 0),
                    "by_op": c.get("by_op") or {}}
        return None
    total_fl = sum(v.get("flops", 0) for v in bd.values())
    total_by = sum(v.get("bytes", 0) for v in bd.values())
    c = bd["collective"]
    return {"flop_fraction": (c.get("flops", 0) / total_fl
                              if total_fl else 0.0),
            "byte_fraction": (c.get("bytes", 0) / total_by
                              if total_by else 0.0),
            "ops": c.get("count", 0),
            "by_op": c.get("by_op") or {}}


def latest_serve_record(recs):
    """The newest serving record (such lines carry no xprof key, so
    they need their own selector)."""
    for r in reversed(recs):
        if (r.get("metric") == "serve_goodput_rps"
                or "latency_decomposition_ms" in r):
            return r
    return None


def render_serve(rec):
    """Serving view: the goodput/SLO headline, per-request latency
    decomposition (queue / sched-idle / h2d / dispatch / pad-waste /
    d2h), the adaptive-wait trajectory, the per-lane table, and the
    offered-load sweep table."""
    out = ["serving: %.1f req/s (goodput at %sms SLO: %.1f), "
           "p50 %.2fms  p99 %.2fms  p999 %.2fms"
           % (rec.get("requests_per_sec") or 0,
              ("%g" % rec["slo_ms"]) if rec.get("slo_ms") else "no",
              rec.get("goodput_rps_at_slo") or 0,
              rec.get("p50_ms") or 0, rec.get("p99_ms") or 0,
              rec.get("p999_ms") or 0),
           "buckets %s  dp=%s  mean occupancy %.1f%%  compiles %s  "
           "steady-state retraces %s  dispatches/batch %s"
           % (rec.get("buckets"), rec.get("dp"),
              100.0 * (rec.get("mean_batch_occupancy") or 0.0),
              rec.get("compiles"), rec.get("steady_state_retraces"),
              rec.get("dispatches_per_request_batch"))]
    if rec.get("adaptive") is not None:
        qd = rec.get("queue_depth") or {}
        out.append("adaptive %s  wait %.2fms  queue depth p50 %s  "
                   "p99 %s  max %s"
                   % ("on" if rec.get("adaptive") else "off",
                      rec.get("adaptive_wait_ms") or 0.0,
                      qd.get("p50", "-"), qd.get("p99", "-"),
                      qd.get("max", "-")))
    out.append("")
    dec = rec.get("latency_decomposition_ms") or {}
    if dec:
        order = ("queue_ms", "sched_idle_ms", "h2d_ms", "dispatch_ms",
                 "pad_waste_ms", "d2h_ms", "request_ms")
        rows = [("stage", "mean", "p50", "p99")]
        for k in order:
            h = dec.get(k)
            if not h:
                continue
            rows.append((k[:-3], "%.3f" % (h.get("mean") or 0),
                         "%.3f" % (h.get("p50") or 0),
                         "%.3f" % (h.get("p99") or 0)))
        out.append("per-request latency decomposition (ms):")
        out += _table(rows)
        out.append("")
    tiers = rec.get("tiers") or []
    if tiers:
        rows = [("offered", "achieved", "goodput", "p50_ms", "p99_ms",
                 "p999_ms", "slo")]
        for t in tiers:
            rows.append(("%g" % t.get("offered_rps", 0),
                         "%.1f" % t.get("achieved_rps", 0),
                         "%.1f" % t.get("goodput_rps", 0),
                         "%.2f" % t.get("p50_ms", 0),
                         "%.2f" % t.get("p99_ms", 0),
                         "%.2f" % t.get("p999_ms", 0),
                         "ok" if t.get("slo_ok") else "BREACH"))
        out.append("offered-load sweep (req/s):")
        out += _table(rows)
        out.append("")
    lanes = rec.get("lanes") or {}
    if lanes:
        rows = [("lane", "offered", "goodput", "deadline_ms", "served",
                 "shed", "p50_ms", "p99_ms")]
        for name, ln in sorted(lanes.items()):
            rows.append((name, "%.1f" % (ln.get("offered_rps") or 0),
                         "%.1f" % (ln.get("goodput_rps") or 0),
                         "%g" % (ln.get("deadline_ms") or 0),
                         str(ln.get("served", "-")),
                         str(ln.get("shed", "-")),
                         "%.2f" % (ln.get("p50_ms") or 0),
                         "%.2f" % (ln.get("p99_ms") or 0)))
        out.append("per-lane goodput (mixed workload):")
        out += _table(rows)
        out.append("")
    traj = rec.get("adaptive_wait_trajectory") or []
    if traj:
        # downsample to ~16 rows: enough to see the controller ramp,
        # collapse and recovery without drowning the report
        step = max(1, len(traj) // 16)
        rows = [("t_s", "wait_ms", "depth", "rows", "bucket", "occ",
                 "reason")]
        for p in traj[::step]:
            rows.append(("%.2f" % (p.get("t_s") or 0),
                         "%.2f" % (p.get("wait_ms") or 0),
                         str(p.get("queue_depth", "-")),
                         str(p.get("rows", "-")),
                         str(p.get("bucket", "-")),
                         "%.2f" % (p.get("occupancy") or 0),
                         str(p.get("reason", "-"))))
        out.append("adaptive-wait trajectory (sampled):")
        out += _table(rows)
        out.append("")
    tp = rec.get("tp") or {}
    if tp:
        if tp.get("incomplete"):
            out.append("tensor-parallel serving: INCOMPLETE: %s"
                       % tp["incomplete"])
            out.append("")
        else:
            out.append(
                "tensor-parallel serving (tp=%s dp=%s): %.1f req/s  "
                "p50 %.2fms  p99 %.2fms  param bytes/device %.2fx  "
                "dispatches/batch %s"
                % (tp.get("tp"), tp.get("dp"),
                   tp.get("goodput_rps") or 0, tp.get("p50_ms") or 0,
                   tp.get("p99_ms") or 0,
                   tp.get("param_bytes_ratio") or 0,
                   tp.get("dispatches_per_request_batch")))
            coll = tp.get("collective") or {}
            by_op = coll.get("by_op") or {}
            out.append(
                "in-graph collectives: %s ops, %s bytes (%.1f%% of "
                "HLO bytes)%s"
                % (coll.get("count", 0), coll.get("bytes", 0),
                   100.0 * (tp.get("collective_bytes_fraction") or 0),
                   "  [%s]" % ", ".join(
                       "%s x%d" % (op, v.get("count", 0))
                       for op, v in sorted(by_op.items()))
                   if by_op else ""))
            pf = tp.get("preflight") or {}
            if pf:
                out.append(
                    "preflight vs simulated %s-byte chip: replicated "
                    "pack %s, tp pack fits (headroom %s bytes)"
                    % (pf.get("simulated_limit_bytes"),
                       "REFUSED" if pf.get("replicated_refused")
                       else "fit (?)", pf.get("tp_headroom_bytes")))
            rf = tp.get("refresh") or {}
            if rf:
                out.append(
                    "delta weight stream: full re-pack %s bytes -> "
                    "delta %s bytes (%.1f%% moved; %s changed / %s "
                    "skipped params)"
                    % (rf.get("full_bytes"), rf.get("delta_bytes"),
                       100.0 * (rf.get("delta_bytes_ratio") or 0),
                       rf.get("changed_params"),
                       rf.get("skipped_params")))
            out.append("")
    if rec.get("incomplete"):
        out.append("INCOMPLETE: %s" % rec["incomplete"])
    return "\n".join(out) + "\n"


def latest_fleet_record(recs):
    """The newest fleet record."""
    for r in reversed(recs):
        if r.get("metric") == "fleet_goodput_rps" or "chaos" in r:
            return r
    return None


def render_fleet(rec):
    """Fleet view: goodput vs replica count, the killed-replica
    recovery window, and the rolling-swap purity proof."""
    out = ["fleet: %.1f req/s best (%s replicas)  chaos %s  swap %s"
           % (rec.get("value") or 0, rec.get("replicas_best"),
              "OK" if rec.get("chaos_ok") else "FAILED",
              "OK" if rec.get("swap_ok") else "FAILED"), ""]
    scaling = rec.get("scaling") or []
    if scaling:
        rows = [("replicas", "offered", "achieved", "p50_ms", "p99_ms",
                 "errors")]
        for t in scaling:
            rows.append((str(t.get("replicas")),
                         "%g" % t.get("offered_rps", 0),
                         "%.1f" % t.get("achieved_rps", 0),
                         "%.2f" % (t.get("p50_ms") or 0),
                         "%.2f" % (t.get("p99_ms") or 0),
                         str(t.get("errors", 0))))
        out.append("goodput vs replica count:")
        out += _table(rows)
        out.append("")
    c = rec.get("chaos") or {}
    if c:
        out.append("killed-replica window:")
        out.append("  pre-kill %.1f req/s -> min %.1f req/s in window, "
                   "recovered to 90%% in %sms"
                   % (c.get("pre_kill_goodput_rps") or 0,
                      c.get("kill_window_min_goodput_rps") or 0,
                      c.get("recovery_ms")))
        out.append("  client errors %s  crashes %s  respawns %s  "
                   "retries %s  recovered requests %s"
                   % (c.get("client_errors"), c.get("replica_crashes"),
                      c.get("respawns"), c.get("retries"),
                      c.get("recovered_requests")))
        out.append("")
    s = rec.get("swap") or {}
    if s:
        out.append("rolling param swap under load (torn_swap armed):")
        out.append("  %s responses: %s old / %s new / %s MIXED, "
                   "%s failed; %s swaps, torn window injected %sx"
                   % (s.get("responses"), s.get("old_version"),
                      s.get("new_version"), s.get("mixed_version"),
                      s.get("failed"), s.get("swaps"),
                      s.get("torn_injected")))
        out.append("")
    if rec.get("incomplete"):
        out.append("INCOMPLETE: %s" % rec["incomplete"])
    return "\n".join(out) + "\n"


def render_wire(rec):
    """Wire view over a fleet record's socket part: the
    serialization-vs-pickle headline, the socket-vs-pipe overhead
    claim, a per-peer transport table (frames, bytes, rtt, reconnects,
    backpressure stalls), and the netfeed epoch. INCOMPLETE-safe: a
    record whose socket phase never ran renders its marker instead of
    crashing the report."""
    if rec.get("incomplete"):
        return "wire: INCOMPLETE: %s\n" % rec["incomplete"]
    sock = rec.get("socket")
    if not sock:
        return "wire: no socket record in this fleet record\n"
    if sock.get("incomplete"):
        return "wire: INCOMPLETE: %s\n" % sock["incomplete"]
    out = ["wire: %.1f req/s over TCP  p99 %.2fx of pipe  chaos "
           "goodput %s%%  [%s]"
           % (sock.get("goodput_rps") or 0,
              sock.get("overhead_p99_x") or 0,
              round(100 * (sock.get("chaos_goodput_ratio") or 0), 1),
              "OK" if rec.get("socket_ok") else "FAILED"), ""]
    ser = sock.get("serialization") or {}
    if ser:
        out.append("serialization (%.2f MB payload, ms/MB):"
                   % (ser.get("payload_mb") or 0))
        rows = [("codec", "encode", "decode"),
                ("wire frames", "%.4f" % (ser.get("wire_encode_ms_per_mb")
                                          or 0),
                 "%.4f" % (ser.get("wire_decode_ms_per_mb") or 0)),
                ("pickle", "%.4f" % (ser.get("pickle_ms_per_mb") or 0),
                 "%.4f" % (ser.get("unpickle_ms_per_mb") or 0))]
        out += _table(rows)
        out.append("")
    rows = [("peer", "pool", "frames", "MB", "rtt_mean", "rtt_p99",
             "reconnects", "bp_stalls")]
    for phase in ("clean", "chaos"):
        w = (sock.get(phase) or {}).get("wire")
        if not w:
            continue
        rtt = w.get("rtt_ms") or {}
        rows.append(("%s/%s" % (phase, w.get("peer", "?")),
                     str(w.get("pool")),
                     "%d/%d" % (w.get("frames_tx", 0),
                                w.get("frames_rx", 0)),
                     "%.1f" % ((w.get("bytes_tx", 0)
                                + w.get("bytes_rx", 0)) / 1048576.0),
                     "-" if rtt.get("mean") is None
                     else "%.2f" % rtt["mean"],
                     "-" if rtt.get("p99") is None
                     else "%.2f" % rtt["p99"],
                     str(w.get("reconnects", 0)),
                     str(w.get("backpressure_stalls", 0))))
    if len(rows) > 1:
        out.append("per-peer transport (frames tx/rx, rtt in ms):")
        out += _table(rows)
        out.append("")
    for phase in ("pipe", "clean", "chaos"):
        t = sock.get(phase) or {}
        if t:
            out.append("  %-5s %6.1f req/s  p50 %sms  p99 %sms  "
                       "errors %s"
                       % (phase, t.get("achieved_rps") or 0,
                          t.get("p50_ms"), t.get("p99_ms"),
                          t.get("errors")))
    inj = (sock.get("chaos") or {}).get("injected") or {}
    if inj:
        out.append("  chaos injected: %s" % ", ".join(
            "%s x%d" % (k, v) for k, v in sorted(inj.items())))
    out.append("")
    nf = sock.get("netfeed") or {}
    if nf.get("incomplete"):
        out.append("netfeed: INCOMPLETE: %s" % nf["incomplete"])
    elif nf:
        out.append("netfeed epoch (2-process, loopback):")
        out.append("  %s batches, %.1f MB in %.2fs (%.1f MB/s); "
                   "feed stall p50 %sms p99 %sms"
                   % (nf.get("batches"), nf.get("payload_mb") or 0,
                      nf.get("epoch_s") or 0,
                      nf.get("goodput_mb_s") or 0,
                      nf.get("feed_stall_p50_ms"),
                      nf.get("feed_stall_p99_ms")))
    return "\n".join(out) + "\n"


def render_fleet_health(rec):
    """Fleet-health view over an obswatch artifact:
    the federated rollup table — one row per replica plus the fleet
    row — the federation-agreement numbers, and the SLO burn-rate
    verdict. INCOMPLETE-safe: a stamped-incomplete record renders its
    marker instead of crashing the report."""
    if rec.get("incomplete"):
        return ("fleet-health: INCOMPLETE: %s\n" % rec["incomplete"])
    fed = rec.get("federation") or {}
    rollup = rec.get("final_rollup") or {}
    fleet = rollup.get("fleet") or {}
    burn = rec.get("burn") or {}
    out = ["fleet-health: %s replicas up / %s, %.1f req/s federated "
           "goodput" % (fleet.get("up", "?"),
                        fleet.get("replicas", "?"),
                        fed.get("fed_goodput_rps") or 0), ""]
    rows = [("replica", "status", "state", "breaker", "served",
             "breaches", "in_flight", "p50_ms", "p99_ms")]

    def _ms(v):
        return "-" if v is None else "%.2f" % v

    for rid, r in sorted((rollup.get("replica_rows") or {}).items()):
        rows.append((rid, str(r.get("status")), str(r.get("state")),
                     str(r.get("breaker")), str(r.get("served")),
                     str(r.get("slo_breaches")),
                     "%g" % (r.get("in_flight") or 0),
                     _ms(r.get("p50_ms")), _ms(r.get("p99_ms"))))
    rows.append(("FLEET", "-", "-",
                 "%s open" % fleet.get("breakers_open", 0),
                 str(fleet.get("served")),
                 str(fleet.get("slo_breaches")),
                 "%g" % (fleet.get("in_flight") or 0),
                 _ms(fleet.get("p50_ms")), _ms(fleet.get("p99_ms"))))
    out.append("federated rollup (per-replica scheduler view; FLEET "
               "row = router-view merge):")
    out += _table(rows)
    out.append("")
    if fed:
        out.append("federation agreement vs client-measured:")
        out.append("  goodput %.1f vs %.1f req/s (%.2f%% off)   "
                   "p99 %.2f vs %.2f ms (%.2f%% off)"
                   % (fed.get("fed_goodput_rps") or 0,
                      fed.get("client_goodput_rps") or 0,
                      100 * (fed.get("goodput_rel_err") or 0),
                      fed.get("fed_p99_ms") or 0,
                      fed.get("client_p99_ms") or 0,
                      100 * (fed.get("p99_rel_err") or 0)))
        out.append("")
    if burn:
        if burn.get("alert_fired"):
            out.append("SLO burn: ALERT at +%ss (fast %.2fx / slow "
                       "%.2fx over budget rate), %.0f%% of error "
                       "budget spent at alert"
                       % (burn.get("alert_at_s"),
                          burn.get("fast_burn") or 0,
                          burn.get("slow_burn") or 0,
                          100 * (burn.get("budget_spent_at_alert")
                                 or 0)))
        else:
            out.append("SLO burn: no alert")
        out.append("")
    series = rec.get("series") or {}
    pts = series.get("burn.budget_spent") or []
    if pts:
        out.append("budget burn-down (%d rollups in store):" % len(pts))
        t0 = pts[0][0]
        for ts, v in pts[-8:]:
            out.append("  +%6.2fs  spent %5.1f%%"
                       % (ts - t0, 100 * float(v or 0)))
    return "\n".join(out) + "\n"


def render_health_rows(rows, top=10):
    """The last-K numwatch health rows (a crash dump's numwatch.jsonl):
    the model's numeric trajectory into the failure."""
    if not rows:
        return ""
    out = ["last-%d model-health rows (numwatch fetches):" % min(
        len(rows), top)]
    t = [("step", "loss", "grad_norm", "uw_max", "nonfinite",
          "bad_tensor", "skips", "rollbacks")]

    def _f(v, fmt="%.4g"):
        if v is None:
            return "-"
        try:
            return fmt % v
        except TypeError:
            return str(v)

    for r in rows[-top:]:
        t.append((str(r.get("step", "?")), _f(r.get("loss")),
                  _f(r.get("grad_norm")), _f(r.get("uw_max")),
                  str(r.get("nonfinite", 0)),
                  str(r.get("bad_tensor") or "-"),
                  str(r.get("skips", 0)), str(r.get("rollbacks", 0))))
    out += _table(t)
    return "\n".join(out) + "\n"


def render_numerics(rec):
    """Numerics view over a numwatch artifact: the per-tensor
    health table (norm / max-abs / nonfinite / zero-frac /
    update-to-weight ratio), the measured stats-on overhead and the
    one-dispatch proof, the guard counters, and the provenance verdict
    when something went nonfinite. INCOMPLETE-safe: a stamped-
    incomplete record renders its marker instead of crashing."""
    if rec.get("incomplete"):
        return "numerics: INCOMPLETE: %s\n" % rec["incomplete"]
    out = ["numerics: stats-on overhead %.2f%% (baseline %.3f ms -> "
           "armed %.3f ms per fused step)"
           % (rec.get("overhead_pct") or 0,
              rec.get("baseline_step_ms") or 0,
              rec.get("armed_step_ms") or 0)]
    out.append("  dispatches/step %.3f   fused_recompiles %s   "
               "overhead gate (<=3%%): %s"
               % (rec.get("dispatches_per_step") or 0,
                  rec.get("fused_recompiles", "?"),
                  "PASS" if rec.get("overhead_ok") else "FAIL"))
    out.append("")
    tensors = rec.get("tensors") or []
    if tensors:
        rows = [("tensor", "grad_l2", "grad_maxabs", "nonfinite",
                 "zero_frac", "uw_ratio")]
        for t in tensors:
            rows.append((str(t.get("name")),
                         "%.4g" % (t.get("grad_l2") or 0),
                         "%.4g" % (t.get("grad_maxabs") or 0),
                         str(t.get("nonfinite", 0)),
                         "%.3f" % (t.get("zero_frac") or 0),
                         "%.3g" % (t.get("uw_ratio") or 0)))
        out.append("per-tensor health (forward order):")
        out += _table(rows)
        out.append("")
    guard = rec.get("guard") or {}
    out.append("guard: %s skipped steps, %s rollbacks"
               % (guard.get("skipped", 0), guard.get("rollbacks", 0)))
    prov = rec.get("provenance")
    if prov:
        out.append("provenance: first bad tensor %s (%s, step %s)"
                   % (prov.get("name"), prov.get("kind"),
                      prov.get("step")))
    health = rec.get("health_rows") or []
    if health:
        out.append("")
        out.append(render_health_rows(health).rstrip())
    return "\n".join(out) + "\n"


# ---------------------------------------------------------------------------
# distributed-trace views (dtrace span trees in a merged chrome trace)
# ---------------------------------------------------------------------------

#: the serving tier's exact latency decomposition, in wall order —
#: these five child spans partition their serve.request parent
FIVE_COMPONENTS = ("serve.queue", "serve.sched_idle", "serve.h2d",
                   "serve.dispatch", "serve.d2h")


def load_chrome_trace(path):
    """Event list from a chrome-trace json ({"traceEvents": [...]} or
    a bare list)."""
    with open(path) as f:
        doc = json.load(f)
    evs = doc.get("traceEvents") if isinstance(doc, dict) else doc
    return [e for e in (evs or []) if isinstance(e, dict)]


def dtrace_trees(events):
    """``{trace_id: [span, ...]}`` from the dtrace ``X`` events of a
    merged chrome trace (mxnet_tpu.dtrace.write_chrome_trace output);
    ts/dur stay in the file's microseconds."""
    trees = {}
    for e in events:
        if e.get("ph") != "X" or e.get("cat") != "dtrace":
            continue
        args = e.get("args") or {}
        tid = args.get("trace")
        if not tid:
            continue
        trees.setdefault(tid, []).append({
            "span": args.get("span"),
            "parent": args.get("parent") or "",
            "name": e.get("name"), "pid": e.get("pid"),
            "ts": float(e.get("ts", 0.0)),
            "dur": float(e.get("dur", 0.0)),
            "kept": args.get("kept"),
            "tags": {k: v for k, v in args.items()
                     if k not in ("trace", "span", "parent", "kept")}})
    return trees


def _span_label(s):
    tags = s["tags"]
    bits = []
    for k in ("request_id", "attempt", "replica", "hedge", "won",
              "abandoned", "breaker", "bucket", "occupancy", "compile",
              "slo_breach", "shed", "pad_rows", "error"):
        if k in tags and tags[k] is not None:
            v = tags[k]
            bits.append(k if v is True else "%s=%s" % (k, v))
    return "  [%s]" % ", ".join(bits) if bits else ""


def render_waterfall(trace_id, spans):
    """One kept trace as an indented tree: per-span wall offset from
    the root (ms), duration, owning pid, and the load-bearing tags;
    under each traced serve.request, the five-component decomposition
    line whose parts sum to the request span by construction."""
    by_id = {s["span"]: s for s in spans}
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s["parent"], []).append(s)
    roots = sorted((s for s in spans if s["parent"] not in by_id),
                   key=lambda s: s["ts"])
    if not roots:
        return "trace %s: no spans\n" % trace_id
    t0 = roots[0]["ts"]
    pids = sorted({s["pid"] for s in spans})
    out = ["trace %s  kept=%s  %d spans across %d processes %s"
           % (trace_id, roots[0].get("kept"), len(spans), len(pids),
              pids)]

    def walk(s, depth):
        out.append("  %+9.2fms %s%-22s %9.2fms  pid %-8s%s"
                   % ((s["ts"] - t0) / 1e3, "  " * depth,
                      s["name"], s["dur"] / 1e3, s["pid"],
                      _span_label(s)))
        for c in sorted(by_parent.get(s["span"], ()),
                        key=lambda c: (c["ts"], c["name"])):
            walk(c, depth + 1)

    for r in roots:
        walk(r, 0)
    for s in spans:
        if s["name"] != "serve.request":
            continue
        comp = {c["name"]: c["dur"] for c in by_parent.get(s["span"], ())
                if c["name"] in FIVE_COMPONENTS}
        if len(comp) == len(FIVE_COMPONENTS):
            total = sum(comp.values())
            out.append("")
            out.append("  decomposition of serve.request %s (pid %s):"
                       % (s["tags"].get("request_id", "?"), s["pid"]))
            out.append("    " + " + ".join(
                "%s %.2fms" % (n.split(".", 1)[1], comp[n] / 1e3)
                for n in FIVE_COMPONENTS)
                + " = %.2fms (request span %.2fms)"
                % (total / 1e3, s["dur"] / 1e3))
    return "\n".join(out) + "\n"


def _dominant_span(spans):
    """The longest non-root span of a tree — where the time actually
    went (leaf spans preferred: a parent always outlasts its pieces)."""
    parents = {s["parent"] for s in spans}
    leaves = [s for s in spans
              if s["parent"] and s["span"] not in parents]
    pool = leaves or [s for s in spans if s["parent"]] or spans
    return max(pool, key=lambda s: s["dur"])


def render_trace_summary(trees, top=3):
    """Top-``top`` slowest kept traces with their dominant span — a
    teaser pointing at the full waterfall view."""
    ranked = []
    for tid, spans in trees.items():
        by_id = {s["span"]: s for s in spans}
        roots = [s for s in spans if s["parent"] not in by_id]
        if not roots:
            continue
        root = max(roots, key=lambda s: s["dur"])
        ranked.append((root["dur"], tid, root, spans))
    ranked.sort(key=lambda t: -t[0])
    out = ["%d kept trace(s); top %d slowest:"
           % (len(ranked), min(top, len(ranked)))]
    rows = [("trace", "root_ms", "kept", "spans", "dominant")]
    for dur, tid, root, spans in ranked[:top]:
        dom = _dominant_span(spans)
        rows.append((tid[:16], "%.2f" % (dur / 1e3),
                     str(root.get("kept")), str(len(spans)),
                     "%s (%.2fms, pid %s)"
                     % (dom["name"], dom["dur"] / 1e3, dom["pid"])))
    out += _table(rows)
    out.append("(full tree: python tools/trace_report.py --view "
               "waterfall <trace>)")
    return "\n".join(out) + "\n"


def render_compile(rec):
    """Per-site compile registry table: each site's newest build by
    what it is (the module text's hash, its Pallas kernels), which cache
    answered and its seconds by phase (``xprof.diff_builds`` tells two
    files' records of one site apart)."""
    xp = rec.get("xprof") or {}
    sites = xp.get("sites") or {}
    if not sites:
        return "no xprof compile records\n"
    rows = [("site", "compiles", "total_s", "last_s", "cache", "trace_s",
             "lower_s", "read_s", "backend_s", "module", "kernels", "flops",
             "held_bytes")]

    def seconds(last, field):
        v = last.get(field)
        return "-" if v is None else "%.3f" % v

    for name, s in sorted(sites.items()):
        last = s.get("last") or {}
        kernels = last.get("kernels")
        rows.append((name, str(s.get("compiles", 0)),
                     "%.3f" % s.get("compile_time_s", 0.0),
                     "%.3f" % (last.get("compile_time_s") or 0.0),
                     last.get("cache") or "-",
                     seconds(last, "trace_s"), seconds(last, "lower_s"),
                     seconds(last, "cache_read_s"),
                     seconds(last, "backend_compile_s"),
                     (last.get("module_sha") or "-")[:12],
                     "-" if kernels is None else str(len(kernels)),
                     "%.3g" % (last.get("flops") or 0),
                     _fmt_bytes(last.get("held_bytes") or 0)))
    out = ["compile registry (%d sites, %d compiles, %.3fs total):"
           % (len(sites), (xp.get("totals") or {}).get("compiles", 0),
              (xp.get("totals") or {}).get("compile_time_s", 0.0)), ""]
    out += _table(rows)
    causes = [(n, (s.get("last") or {}).get("retrace_cause"))
              for n, s in sorted(sites.items())]
    causes = [(n, c) for n, c in causes if c]
    if causes:
        out.append("")
        out.append("retrace causes:")
        out += ["  %s: %s" % (n, c) for n, c in causes]
    return "\n".join(out) + "\n"


def render_ops(rec):
    """Per-category FLOP+bytes breakdown of the main executable; the
    TOTAL row equals the sum of the category rows by construction."""
    xp = rec.get("xprof") or {}
    site, s = _main_site(xp)
    bd = ((s.get("last") or {}).get("op_breakdown")) or {}
    if not bd:
        return "no op-category breakdown recorded\n"
    total_fl = sum(v.get("flops", 0) for v in bd.values())
    total_by = sum(v.get("bytes", 0) for v in bd.values())
    total_n = sum(v.get("count", 0) for v in bd.values())
    rows = [("category", "flops", "share", "bytes", "ops")]
    for cat, v in sorted(bd.items(), key=lambda kv: -kv[1].get("flops", 0)):
        rows.append((cat, str(v.get("flops", 0)),
                     "%.1f%%" % (100.0 * v.get("flops", 0)
                                 / total_fl if total_fl else 0.0),
                     _fmt_bytes(v.get("bytes", 0)),
                     str(v.get("count", 0))))
    rows.append(("TOTAL", str(total_fl), "100.0%",
                 _fmt_bytes(total_by), str(total_n)))
    out = ["op categories for %s:" % site, ""] + _table(rows)
    ana = xp.get("bench_analysis") or {}
    if ana.get("arithmetic_intensity") is not None:
        out.append("")
        out.append("arithmetic intensity %.2f FLOP/B (ridge %s) -> %s"
                   % (ana["arithmetic_intensity"],
                      "%.2f" % ana["ridge_intensity"]
                      if ana.get("ridge_intensity") else "unknown",
                      "%s-bound" % ana.get("bound", "unknown")))
    return "\n".join(out) + "\n"


def render_memory(rec):
    """Per-site memory_analysis table + the HBM watermark/headroom."""
    xp = rec.get("xprof") or {}
    sites = xp.get("sites") or {}
    out = []
    if sites:
        rows = [("site", "arg", "out", "temp", "held")]
        for name, s in sorted(sites.items()):
            last = s.get("last") or {}
            rows.append((name,
                         _fmt_bytes(last.get("argument_bytes") or 0),
                         _fmt_bytes(last.get("output_bytes") or 0),
                         _fmt_bytes(last.get("temp_bytes") or 0),
                         _fmt_bytes(last.get("held_bytes") or 0)))
        out += ["memory analysis per executable:", ""] + _table(rows)
    hbm = xp.get("hbm") or {}
    peak = rec.get("peak_hbm_bytes")
    if hbm or peak is not None:
        out.append("")
        out.append("hbm: live %s  run-peak %s  limit %s  headroom %s "
                   "(source: %s)"
                   % (_fmt_bytes(hbm.get("live_bytes") or 0),
                      _fmt_bytes(peak or 0),
                      _fmt_bytes(hbm["limit_bytes"])
                      if hbm.get("limit_bytes") else "n/a",
                      _fmt_bytes(hbm["limit_bytes"]
                                 - (hbm.get("live_bytes") or 0))
                      if hbm.get("limit_bytes") else "n/a",
                      hbm.get("source", "?")))
    return ("\n".join(out) + "\n") if out else "no xprof memory data\n"


def render_bench_report(rec, top=10):
    """Full bench view: the MFU-gap headline first, then compile, ops
    and memory."""
    return "\n".join([render_bench_summary(rec), render_compile(rec),
                      render_ops(rec), render_memory(rec)])


def categorize_op(name):
    """Map a profiler-trace op name onto the same
    categories the HLO breakdown uses, so device time and analytic
    FLOPs line up in one table."""
    n = name.lower()
    if "conv" in n:
        return "conv"
    if "dot" in n or "einsum" in n or "matmul" in n:
        return "dot"
    if any(k in n for k in ("all-reduce", "all-gather", "all-to-all",
                            "reduce-scatter", "collective", "permute",
                            "allreduce", "allgather")):
        return "collective"
    if "fusion" in n:
        return "fusion"
    if any(k in n for k in ("transpose", "copy", "reshape", "broadcast",
                            "slice", "concatenate", "pad", "gather",
                            "scatter", "bitcast", "iota")):
        return "transpose"
    if any(k in n for k in ("add", "sub", "mul", "div", "max", "min",
                            "exp", "log", "tanh", "sqrt", "rsqrt",
                            "compare", "select", "convert", "reduce",
                            "rng", "neg", "abs")):
        return "elementwise"
    return "other"


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def report_crash_dump(dump_dir, top=10):
    """Full report for one flight-recorder dump directory."""
    out = []
    meta_path = os.path.join(dump_dir, "meta.json")
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
        out.append("flight recorder dump: %s" % dump_dir)
        out.append("  reason: %s  pid: %s  rank: %s  steps: %s"
                   % (meta.get("reason"), meta.get("pid"),
                      meta.get("rank"), meta.get("steps_recorded")))
        if meta.get("exception"):
            out.append("  exception:")
            out.extend("    " + l for l in
                       meta["exception"].rstrip().splitlines())
        out.append("")
        events = meta.get("events", [])
    else:
        events = []
    steps_path = os.path.join(dump_dir, "steps.jsonl")
    if os.path.exists(steps_path):
        out.append(render(load_records(steps_path), top=top))
    tel_path = os.path.join(dump_dir, "telemetry.json")
    if os.path.exists(tel_path):
        with open(tel_path) as f:
            tel = json.load(f)
        locks = render_locks(tel)
        if locks:
            out.append(locks)
        ckpt = render_ckpt(tel)
        if ckpt:
            out.append(ckpt)
    nw_path = os.path.join(dump_dir, "numwatch.jsonl")
    if os.path.exists(nw_path):
        health = render_health_rows(load_records(nw_path), top=top)
        if health:
            out.append(health)
    out.append(render_events(events))
    return "\n".join(out)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("path", nargs="?",
                   help="step-trace .jsonl, BENCH .json, crash-dump "
                        "dir, or (--view waterfall) a trace id or "
                        "chrome-trace path")
    p.add_argument("--top", type=int, default=10,
                   help="slowest steps to show (default 10)")
    p.add_argument("--view", default="steps",
                   choices=("steps", "compile", "ops", "memory", "bench",
                            "serve", "fleet", "fleet-health", "wire",
                            "waterfall", "numerics"),
                   help="steps (default): slowest-step trace table; "
                        "compile/ops/memory/bench: xprof views over a "
                        "file of records that carry an xprof summary; "
                        "serve: latency decomposition + load sweep over "
                        "a serving record; fleet: recovery window + "
                        "swap purity over a fleet record; wire: socket-"
                        "transport per-peer table + netfeed epoch over "
                        "a fleet record; fleet-health: federated rollup "
                        "table + burn-rate verdict over an obswatch "
                        "artifact; "
                        "waterfall: one kept distributed trace as an "
                        "indented span tree (path = a chrome-trace "
                        "file, or a trace id resolved against "
                        "FLEET_trace.json in the repo root); numerics: "
                        "per-tensor model-health table + overhead "
                        "verdict over a numwatch artifact")
    a = p.parse_args(argv)
    if a.view == "waterfall":
        # positional: a trace id (or unique prefix) resolved against
        # FLEET_trace.json in the repo root, or a chrome-trace path
        # (then the slowest kept tree renders)
        tid, path = a.path, None
        if tid and os.path.exists(tid):
            path, tid = tid, None
        if path is None:
            path = os.path.join(_repo_root(), "FLEET_trace.json")
        if not os.path.exists(path):
            sys.stdout.write("no chrome trace at %s (write one with "
                             "mxnet_tpu.dtrace.write_chrome_trace)\n"
                             % path)
            return 1
        trees = dtrace_trees(load_chrome_trace(path))
        if not trees:
            sys.stdout.write("no dtrace span trees in %s\n" % path)
            return 1
        if tid is not None:
            hits = [t for t in trees if t.startswith(tid)]
            if len(hits) != 1:
                sys.stdout.write(
                    "trace id %r matches %d of %d kept traces in %s\n"
                    % (tid, len(hits), len(trees), path))
                return 1
            tid = hits[0]
        else:
            tid = max(trees, key=lambda t: max(
                s["dur"] for s in trees[t]))
        sys.stdout.write(render_waterfall(tid, trees[tid]))
        return 0
    if a.path is None:
        p.error("path is required")
    if a.view == "serve":
        rec = latest_serve_record(load_bench_records(a.path))
        if rec is None:
            sys.stdout.write("no serving record in %s\n" % a.path)
            return 1
        sys.stdout.write(render_serve(rec))
        return 0
    if a.view in ("fleet", "wire"):
        rec = latest_fleet_record(load_bench_records(a.path))
        if rec is None:
            sys.stdout.write("no fleet record in %s\n" % a.path)
            return 1
        fn = {"fleet": render_fleet, "wire": render_wire}
        sys.stdout.write(fn[a.view](rec))
        return 0
    if a.view in ("fleet-health", "numerics"):
        if not os.path.exists(a.path):
            sys.stdout.write("no %s artifact at %s\n" % (a.view, a.path))
            return 1
        try:
            with open(a.path) as f:
                rec = json.load(f)
        except ValueError:
            sys.stdout.write("%s: INCOMPLETE: unreadable artifact %s\n"
                             % (a.view, a.path))
            return 0
        fn = {"fleet-health": render_fleet_health,
              "numerics": render_numerics}
        sys.stdout.write(fn[a.view](rec))
        return 0
    if a.view != "steps":
        rec = latest_xprof_record(load_bench_records(a.path))
        if rec is None:
            sys.stdout.write("no record with an xprof summary in %s\n"
                             % a.path)
            return 1
        fn = {"compile": render_compile, "ops": render_ops,
              "memory": render_memory, "bench": render_bench_report}
        sys.stdout.write(fn[a.view](rec))
        return 0
    if os.path.isdir(a.path):
        sys.stdout.write(report_crash_dump(a.path, top=a.top))
    else:
        sys.stdout.write(render(load_records(a.path), top=a.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
