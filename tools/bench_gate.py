#!/usr/bin/env python
"""bench_gate: the perf-regression gate over the checked-in bench
artifact trajectory.

Every chip window leaves artifacts behind — ``BENCH_r*.json`` (the
training headline trajectory), ``SERVE_bench.json``,
``FLEET_bench.json``, ``MULTICHIP_scaling.json`` — but until now nobody
compared a new record against the old ones. This tool does, per
headline metric:

* **Trajectory headlines** (``BENCH_r*.json``): the latest record's
  accelerator-truth ``resnet50_train_imgs_per_sec`` (a cpu-fallback
  record carries it in ``parsed.last_accelerator_result``) against the
  best prior record. The internal baseline IS the trajectory.
* **Single-artifact headlines** (goodput, p99, occupancy, imgs/sec,
  dispatches/step): the artifact's current value against the checked-in
  baseline file (``tools/bench_baselines.json``), refreshed with
  ``--update-baselines`` after an accepted perf change.

A metric regresses when it moves in the WRONG direction by more than
its tolerance (relative); improvements always pass and never fail the
gate. A missing artifact or one stamped ``"incomplete"`` reports
INCOMPLETE — exit 0, so an unattended run that produced
no artifact does not page anyone (``--strict`` upgrades INCOMPLETE to
failure for interactive use).

Exit codes: 0 pass/incomplete, 1 regression (each one named: metric,
artifact, baseline, current, measured delta), 2 usage error. The full
verdict lands in ``BENCH_GATE.json``; ``--progress FILE`` appends a
one-line verdict record (the obs-gate Make target points it at
PROGRESS.jsonl).

Usage::

    python tools/bench_gate.py                      # gate the repo root
    python tools/bench_gate.py --dir D --json       # machine-readable
    python tools/bench_gate.py --update-baselines   # accept current perf
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from typing import Callable, List, Optional

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from mxnet_tpu.checkpoint import atomic_writer  # noqa: E402

DEFAULT_TOLERANCE = 0.10
GATE_ARTIFACT = "BENCH_GATE.json"
BASELINES = os.path.join("tools", "bench_baselines.json")


def _load_json(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _dig(rec: dict, path: str):
    node = rec
    for part in path.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node


def _bench_headline(rec: dict) -> Optional[float]:
    """Accelerator-truth training headline from one BENCH_r*.json: a
    cpu-fallback record gates on the accelerator result it carries
    forward, never on the fallback number (cpu img/s vs TPU img/s is
    not a regression, it is a different machine)."""
    parsed = rec.get("parsed") or {}
    lar = parsed.get("last_accelerator_result") or {}
    if lar.get("value") is not None:
        return float(lar["value"])
    if parsed.get("platform", "").startswith("cpu"):
        return None
    if parsed.get("value") is not None:
        return float(parsed["value"])
    return None


class Spec:
    """One gated headline: where it lives, which way is better."""

    def __init__(self, metric: str, artifact: str, path: str,
                 direction: str, tolerance: float = DEFAULT_TOLERANCE):
        assert direction in ("higher", "lower")
        self.metric = metric
        self.artifact = artifact
        self.path = path
        self.direction = direction
        self.tolerance = tolerance

    def extract(self, rec: dict) -> Optional[float]:
        v = _dig(rec, self.path)
        return None if v is None else float(v)

    def regressed(self, current: float, baseline: float,
                  tolerance: Optional[float] = None) -> bool:
        tol = self.tolerance if tolerance is None else tolerance
        if baseline == 0:
            return False
        delta = (current - baseline) / abs(baseline)
        return (delta < -tol) if self.direction == "higher" \
            else (delta > tol)


SPECS: List[Spec] = [
    Spec("serve_goodput_rps", "SERVE_bench.json", "value", "higher"),
    Spec("serve_p99_ms", "SERVE_bench.json", "p99_ms", "lower"),
    Spec("serve_mean_batch_occupancy", "SERVE_bench.json",
         "mean_batch_occupancy", "higher"),
    # tensor-parallel serving (bench.py serve --tp), merged under the
    # ``tp`` key: goodput at tp>=2 with in-graph resharding, and the
    # delta-aware weight stream — moved bytes over full-pack bytes
    # when one param changed; a drift toward 1.0 means the diff
    # stopped skipping resident shards
    Spec("serve_tp_goodput_rps", "SERVE_bench.json",
         "tp.goodput_rps", "higher"),
    Spec("refresh_delta_bytes_ratio", "SERVE_bench.json",
         "tp.refresh.delta_bytes_ratio", "lower"),
    Spec("fleet_goodput_rps", "FLEET_bench.json", "value", "higher"),
    Spec("fleet_socket_goodput_rps", "FLEET_bench.json",
         "socket.goodput_rps", "higher"),
    Spec("fleet_feed_stall_p99_ms", "FLEET_bench.json",
         "socket.netfeed.feed_stall_p99_ms", "lower", tolerance=0.5),
    Spec("obswatch_fleet_goodput_rps", "OBS_fleet.json", "value",
         "higher"),
    Spec("multichip_imgs_per_sec", "MULTICHIP_scaling.json", "value",
         "higher"),
    Spec("multichip_dispatches_per_step", "MULTICHIP_scaling.json",
         "dispatches_per_step", "lower"),
    # FSDP recipe (bench.py multichip --fsdp): per-device params +
    # opt-state bytes vs replicated — 0.25 at fsdp=4 when every dim 0
    # divides; a ratio drift upward means the recipe stopped sharding
    Spec("fsdp_param_bytes_ratio", "MULTICHIP_scaling.json",
         "fsdp.param_bytes_ratio", "lower"),
    Spec("fsdp_dispatches_per_step", "MULTICHIP_scaling.json",
         "fsdp.dispatches_per_step", "lower"),
    # the checked-in baseline is the CONTRACT (3% overhead), not a
    # measurement; tolerance 1.0 sizes the trip point (>2x the bar) to
    # the one-core host's program-placement noise floor — the exact
    # one-dispatch/one-trace contract is pinned by tier-1 tests, this
    # gate catches gross slowdowns
    Spec("numwatch_overhead_pct", "NUMWATCH_health.json", "value",
         "lower", tolerance=1.0),
    Spec("numwatch_dispatches_per_step", "NUMWATCH_health.json",
         "dispatches_per_step", "lower"),
]


def _check_trajectory(root: str, tolerance: Optional[float],
                      checks: list):
    """BENCH_r*.json: latest accelerator-truth headline vs the best
    prior record — the trajectory is its own baseline."""
    paths = sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))
    points = []
    for p in paths:
        rec = _load_json(p)
        if rec is None:
            continue
        v = _bench_headline(rec)
        if v is not None:
            points.append((os.path.basename(p), v))
    check = {"metric": "resnet50_train_imgs_per_sec",
             "artifact": "BENCH_r*.json", "direction": "higher"}
    if not paths:
        check.update(status="incomplete",
                     detail="no BENCH_r*.json trajectory")
    elif len(points) < 2:
        check.update(status="incomplete",
                     detail="fewer than 2 gateable trajectory points")
    else:
        name, current = points[-1]
        base_name, baseline = max(points[:-1], key=lambda nv: nv[1])
        spec = Spec("resnet50_train_imgs_per_sec", name,
                    "unused", "higher")
        tol = DEFAULT_TOLERANCE if tolerance is None else tolerance
        delta = (current - baseline) / abs(baseline) if baseline else 0.0
        check.update(artifact=name, baseline=baseline,
                     baseline_artifact=base_name, current=current,
                     delta=round(delta, 4), tolerance=tol,
                     status=("fail" if spec.regressed(current, baseline,
                                                      tolerance)
                             else "pass"))
    checks.append(check)


def run_gate(root: str = _ROOT, baselines_path: Optional[str] = None,
             tolerance: Optional[float] = None, strict: bool = False,
             clock: Callable[[], float] = time.time) -> dict:
    """Evaluate every headline; returns the verdict record::

        {"ts", "verdict": "pass"|"fail"|"incomplete", "checks": [...],
         "regressions": [names]}

    ``tolerance`` overrides every spec's tolerance when given;
    ``clock`` is injectable so tests stamp deterministic verdicts."""
    baselines_path = baselines_path or os.path.join(root, BASELINES)
    baselines = _load_json(baselines_path) or {}
    checks: list = []
    _check_trajectory(root, tolerance, checks)
    cache: dict = {}
    for spec in SPECS:
        path = os.path.join(root, spec.artifact)
        if spec.artifact not in cache:
            cache[spec.artifact] = _load_json(path)
        rec = cache[spec.artifact]
        check = {"metric": spec.metric, "artifact": spec.artifact,
                 "direction": spec.direction}
        if rec is None:
            check.update(status="incomplete",
                         detail="artifact missing/unreadable")
            checks.append(check)
            continue
        if rec.get("incomplete"):
            check.update(status="incomplete",
                         detail=str(rec["incomplete"]))
            checks.append(check)
            continue
        current = spec.extract(rec)
        if current is None:
            check.update(status="incomplete",
                         detail="headline %r absent" % spec.path)
            checks.append(check)
            continue
        base = (baselines.get(spec.artifact) or {}).get(spec.metric)
        if base is None or base.get("value") is None:
            check.update(status="no-baseline", current=current)
            checks.append(check)
            continue
        baseline = float(base["value"])
        tol = (base.get("tolerance", spec.tolerance)
               if tolerance is None else tolerance)
        delta = (current - baseline) / abs(baseline) if baseline else 0.0
        check.update(baseline=baseline, current=current,
                     delta=round(delta, 4), tolerance=tol,
                     status=("fail" if spec.regressed(current, baseline,
                                                      tol)
                             else "pass"))
        checks.append(check)
    regressions = [c for c in checks if c["status"] == "fail"]
    incomplete = [c for c in checks if c["status"] == "incomplete"]
    if regressions:
        verdict = "fail"
    elif incomplete and (strict or not any(
            c["status"] == "pass" for c in checks)):
        verdict = "fail" if strict else "incomplete"
    else:
        verdict = "pass"
    return {"ts": round(clock(), 6), "verdict": verdict,
            "tolerance_override": tolerance,
            "checks": checks,
            "regressions": ["%s (%s)" % (c["metric"], c["artifact"])
                            for c in regressions],
            "incomplete": ["%s (%s)" % (c["metric"], c["artifact"])
                           for c in incomplete]}


def update_baselines(root: str = _ROOT,
                     baselines_path: Optional[str] = None) -> dict:
    """Rewrite the checked-in baseline file from the current artifacts
    (atomic replace). Artifacts that are missing or incomplete keep
    their previous baseline entry."""
    baselines_path = baselines_path or os.path.join(root, BASELINES)
    out = _load_json(baselines_path) or {}
    for spec in SPECS:
        rec = _load_json(os.path.join(root, spec.artifact))
        if rec is None or rec.get("incomplete"):
            continue
        v = spec.extract(rec)
        if v is None:
            continue
        out.setdefault(spec.artifact, {})[spec.metric] = {
            "value": v, "direction": spec.direction,
            "tolerance": spec.tolerance,
            "smoke": bool(rec.get("smoke"))}
    data = (json.dumps(out, indent=2, sort_keys=True) + "\n").encode()
    with atomic_writer(baselines_path) as f:
        f.write(data)
    return out


def _render(verdict: dict) -> str:
    lines = ["bench_gate: %s" % verdict["verdict"].upper()]
    for c in verdict["checks"]:
        status = c["status"]
        if status in ("pass", "fail"):
            arrow = {"higher": ">=", "lower": "<="}[c["direction"]]
            lines.append(
                "  [%s] %-32s %s: current=%.4g baseline=%.4g "
                "delta=%+.1f%% (want %s baseline within %.0f%%)"
                % (status.upper(), c["metric"], c["artifact"],
                   c["current"], c["baseline"], 100 * c["delta"],
                   arrow, 100 * c["tolerance"]))
        else:
            lines.append("  [%s] %-32s %s: %s"
                         % (status.upper(), c["metric"], c["artifact"],
                            c.get("detail", "")))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="bench_gate",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--dir", default=_ROOT,
                    help="artifact directory (default: repo root)")
    ap.add_argument("--baselines", default=None,
                    help="baseline file (default: <dir>/%s)" % BASELINES)
    ap.add_argument("--tolerance", type=float, default=None,
                    help="override every headline's relative tolerance")
    ap.add_argument("--strict", action="store_true",
                    help="treat INCOMPLETE as failure (interactive use)")
    ap.add_argument("--json", action="store_true",
                    help="print the verdict record as JSON")
    ap.add_argument("--update-baselines", action="store_true",
                    help="accept current artifact values as baselines")
    ap.add_argument("--no-artifact", action="store_true",
                    help="skip writing %s" % GATE_ARTIFACT)
    ap.add_argument("--progress", default=None,
                    help="append a one-line verdict record to this "
                         "JSONL file")
    args = ap.parse_args(argv)
    if args.update_baselines:
        out = update_baselines(args.dir, args.baselines)
        print("bench_gate: baselines updated (%d artifacts)" % len(out))
        return 0
    verdict = run_gate(args.dir, args.baselines, args.tolerance,
                       strict=args.strict)
    if not args.no_artifact:
        try:
            with open(os.path.join(args.dir, GATE_ARTIFACT), "w") as f:
                json.dump(verdict, f, indent=2, sort_keys=True)
                f.write("\n")
        except OSError:
            pass
    if args.progress:
        line = json.dumps({
            "ts": verdict["ts"], "kind": "bench_gate",
            "verdict": verdict["verdict"],
            "checks": len(verdict["checks"]),
            "regressions": verdict["regressions"]}) + "\n"
        fd = os.open(args.progress,
                     os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        try:
            os.write(fd, line.encode())
        finally:
            os.close(fd)
    print(json.dumps(verdict) if args.json else _render(verdict))
    return 1 if verdict["verdict"] == "fail" else 0


if __name__ == "__main__":
    sys.exit(main())
