"""Central registry for ``MXNET_TPU_*`` environment variables.

PRs 4 and 5 each grew knobs faster than ``docs/env_vars.md`` tracked
them (37 reads in code vs 31 documented at the PR 6 audit). The
reference framework never had this problem because ``dmlc::GetEnv``
call sites were greppable C++ and the docs were generated review
gates; our Python equivalent drifted. This module makes drift
impossible by construction:

* every ``MXNET_TPU_*`` variable is **declared once** here with its
  name, type, default and doc string;
* every **read** goes through :func:`get` (reading an undeclared name
  raises, and ``tools/graftlint.py``'s env-registry pass statically
  rejects any ``os.environ`` / ``base.getenv`` read of a
  ``MXNET_TPU_*`` literal outside this file);
* the ``MXNET_TPU_*`` section of ``docs/env_vars.md`` is **generated**
  from these declarations (:func:`generate_docs` / :func:`sync_docs`),
  and ``tests/test_graftlint.py`` fails tier-1 when the checked-in doc
  block differs from the registry.

Writes (``os.environ[...] = ...`` for child processes, harness env
overrides) are intentionally out of scope: the registry governs how
configuration is *consumed*, not how harnesses stage it.

Non-``MXNET_TPU_`` variables (``MXNET_ENGINE_TYPE``, ``MXTPU_PS_*``,
``JAX_PLATFORMS``) keep their hand-written doc sections and the plain
:func:`mxnet_tpu.base.getenv` accessor.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

__all__ = ["EnvVar", "declare", "get", "is_set", "declared", "var",
           "generate_docs", "sync_docs", "DOC_BEGIN", "DOC_END"]

_UNSET = object()


class EnvVar:
    """One declared environment variable: the (name, type, default,
    doc) record the docs table and the lint pass are generated from."""

    __slots__ = ("name", "type", "default", "doc", "section")

    def __init__(self, name: str, type_: type, default, doc: str,
                 section: str):
        self.name = name
        self.type = type_
        self.default = default
        self.doc = doc
        self.section = section

    def coerce(self, raw: str):
        if self.type is bool:
            return raw.lower() in ("1", "true", "yes", "on")
        if self.type is int:
            return int(raw)
        if self.type is float:
            return float(raw)
        return raw


_REGISTRY: Dict[str, EnvVar] = {}
# section insertion order -> docs section order
_SECTIONS: List[str] = []


def declare(name: str, type_: type, default, doc: str,
            section: str = "General") -> EnvVar:
    """Register ``name``; call once per variable, at module definition
    below (third parties may declare their own under a distinct
    prefix)."""
    if name in _REGISTRY:
        raise ValueError("env var %r declared twice" % name)
    v = EnvVar(name, type_, default, doc, section)
    _REGISTRY[name] = v
    if section not in _SECTIONS:
        _SECTIONS.append(section)
    return v


def var(name: str) -> EnvVar:
    """The declaration record for ``name`` (KeyError if undeclared)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            "env var %r is not declared in mxnet_tpu/env.py; declare it "
            "there (name, type, default, doc) before reading it" % name)


def get(name: str, default: Any = _UNSET):
    """Read a declared variable with type coercion from its declaration.

    ``default`` overrides the declared default for call sites whose
    fallback is dynamic (e.g. host CPU count); the declared default is
    what the docs table shows.
    """
    v = var(name)
    raw = os.environ.get(name)
    if raw is None:
        return v.default if default is _UNSET else default
    return v.coerce(raw)


def is_set(name: str) -> bool:
    """True when the (declared) variable is present in the environment."""
    var(name)
    return name in os.environ


def declared() -> Dict[str, EnvVar]:
    """Name -> declaration, for the docs generator and the lint pass."""
    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# declarations
# ---------------------------------------------------------------------------

_B = "Bench"

declare("MXNET_TPU_FUSED_STEP", bool, False,
        "`Module.fit` (and `FeedForward.fit` through it) compiles forward "
        "+ backward + optimizer update — and, when every metric supports "
        "it, the metric fold — into ONE donated XLA dispatch per batch "
        "instead of three-plus. Falls back to the classic loop for "
        "`dist_*` kvstores, custom-Python-`update` optimizers, installed "
        "monitors, `inputs_need_grad=True`, `grad_req=\"add\"`, and "
        "threaded engines — each fallback counts "
        "`step.fused_fallback.<reason>` and warns once naming the "
        "reason. Default ON (no opt-in needed) under a `device_sync` "
        "kvstore on a multi-device mesh. See \"Fused train step\" and "
        "\"Sharded fused step\" in `performance.md`.",
        section="Fused train step")
declare("MXNET_TPU_DEVICE_SYNC_FUSED", bool, True,
        "Under a `device_sync` kvstore on a multi-device mesh the fused "
        "step is the DEFAULT path: the gradient exchange runs as a "
        "mean-psum GSPMD all-reduce inside the single donated dispatch "
        "(see \"Sharded fused step\" in `performance.md`). Set to 0 to "
        "require the explicit `MXNET_TPU_FUSED_STEP=1` opt-in instead.",
        section="Fused train step")
declare("MXNET_TPU_FUSED_UPDATE", bool, True,
        "Set to 0 to disable the stacked multi-param optimizer update "
        "kernel (one XLA call per param group); also disables the fused "
        "train step, which builds on it.",
        section="Fused train step")
declare("MXNET_TPU_MESH_FSDP", int, 0,
        "Size of the `fsdp` mesh axis. 0/1 keeps the single-axis `dp` "
        "mesh (every device a data-parallel replica, params and "
        "optimizer state fully replicated). N>1 reshapes the device "
        "grid into a named `(dp, fsdp)` mesh (device count must divide "
        "by N): the batch shards over `dp x fsdp` as before, while "
        "params and optimizer-state packs NamedSharding-shard along "
        "`fsdp` (ZeRO-3 style) — GSPMD emits the all-gather before the "
        "forward and the reduce-scatter of the gradients INSIDE the one "
        "donated fused dispatch, so per-device params+opt-state bytes "
        "drop ~1/N and `dispatches_per_step` stays 1.0. See \"Sharding "
        "the model\" in `performance.md`.",
        section="Multi-axis mesh / FSDP")
declare("MXNET_TPU_FSDP_PARAMS", bool, True,
        "Escape hatch for the FSDP recipe: set to 0 to keep params and "
        "optimizer state fully replicated even on a `(dp, fsdp)` mesh "
        "(the batch still shards over both axes — behaviourally plain "
        "data parallelism, for bisecting a sharding suspicion without "
        "changing the mesh shape). Params whose leading dimension does "
        "not divide by the `fsdp` axis size replicate regardless.",
        section="Multi-axis mesh / FSDP")
declare("MXNET_TPU_ENGINE_SYNC", bool, False,
        "Re-enable the engine's `block_until_ready` on fused-step "
        "results. The fused step normally skips that block (its outputs "
        "are freshly donated buffers; blocking would serialize every "
        "batch on device completion) — set when debugging to surface "
        "device errors at the step that caused them.",
        section="Fused train step")
declare("MXNET_TPU_DONATE", bool, True,
        "Set to 0 to disable buffer donation in the fused optimizer "
        "update kernels and the executor's fused fwd+bwd (aux). Default "
        "ON under the inline engines (XLAEngine / NaiveEngine): XLA "
        "writes new params/optimizer state/BN stats into the old HBM "
        "buffers, so training holds one copy instead of a transient two. "
        "Donation auto-disables under threaded engines (a queued reader "
        "could observe a deleted buffer).",
        section="Memory / donation")

declare("MXNET_TPU_DECODE_PROCS", int, 0,
        "Decode with N multiprocessing workers writing into the "
        "shared-memory batch ring (same as constructing "
        "`ImageRecordIter(..., preprocess_mode=\"process\")`; the env "
        "var also sets the worker count). Default 0: the thread pool "
        "(`preprocess_threads`) remains the in-process default. See "
        "\"Input pipeline tuning\" in `performance.md`.",
        section="Input pipeline")
declare("MXNET_TPU_DECODE_RING", int, 0,
        "Batch slots in the shared-memory ring (default "
        "`max(2, 2 x workers)`); the decode-ahead depth, at "
        "`slots x batch_bytes` of /dev/shm.",
        section="Input pipeline")
declare("MXNET_TPU_DECODE_START", str, "spawn",
        "Multiprocessing start method for decode workers (`fork` is "
        "unsafe next to a live TPU client).",
        section="Input pipeline")
declare("MXNET_TPU_DECODE_TIMEOUT", float, 120.0,
        "Seconds the consumer waits on the ring before declaring the "
        "pipeline wedged and falling back to in-process decode.",
        section="Input pipeline")
declare("MXNET_TPU_DEVICE_STAGING", bool, False,
        "`fit()` wraps the training iterator in `DeviceStagingIter`: "
        "`device_put` for batch N+1 is issued while step N executes, "
        "overlapping H2D with compute.",
        section="Input pipeline")
declare("MXNET_TPU_DEVICE_FEED", bool, False,
        "`CachedImageRecordIter` ships raw uint8 stored frames with "
        "deferred augmentation params (`batch.aug`) instead of eagerly "
        "augmented float32 crops: <= 1/3 the H2D bytes, and the fused "
        "train step runs the augmentation inside its single donated "
        "dispatch. Same as constructing the iterator with "
        "`device_feed=True`. Non-fused consumers materialize the batch "
        "transparently; results are bit-identical either way. See "
        "\"Feeding the chip\" in `performance.md`.",
        section="Input pipeline")
declare("MXNET_TPU_AUG_REPLICAS", int, 0,
        "Data-parallel replica count for `CachedImageRecordIter`'s "
        "deferred augmentation draws (same as constructing with "
        "`aug_replicas=N`): crop/mirror params are keyed per (epoch, "
        "batch, replica) so each `dp` shard of a device-feed batch "
        "augments from an independent stream. Default 0 (single "
        "stream, the historical draws).",
        section="Input pipeline")
declare("MXNET_TPU_FEED_DEPTH", int, 0,
        "`fit()` wraps the training iterator in a `FeedScheduler`: a "
        "worker thread keeps N staged batches in flight ahead of the "
        "step loop (generalizes `MXNET_TPU_DEVICE_STAGING`'s double "
        "buffer; subsumes it when both are set). The time each step "
        "blocks on an empty queue lands in the `io.feed_stall_ms` "
        "histogram for StepTrace's dominant-cause labeling. Default 0 "
        "(off); 2-4 absorbs most host-side jitter at N batches of extra "
        "memory.",
        section="Input pipeline")

declare("MXNET_TPU_SANITIZE", str, "",
        "Comma-separated list of runtime sanitizers to arm (`transfer`, "
        "`retrace`, `donation`, `locks`, `deadlock`, or `all`). "
        "`transfer` wraps the fused "
        "step loop in `jax.transfer_guard(\"disallow\")` so any implicit "
        "host<->device transfer (a numpy array leaking into the "
        "dispatch, Python control flow on a device value) raises at the "
        "step that caused it; `retrace` raises when "
        "`step.fused_recompiles` grows after warmup (a silent "
        "steady-state recompile); `donation` verifies donated buffers "
        "were actually consumed by XLA; `locks` wraps the threaded "
        "plane's locks to raise on observed lock-order inversion and "
        "feed `lock.wait_ms` contention histograms; `deadlock` runs a "
        "watchdog thread that dumps all-thread stacks through the "
        "flight recorder when step progress stalls. Trips are counted "
        "under `sanitizer.trips`. See docs/static_analysis.md.",
        section="Runtime sanitizers")
declare("MXNET_TPU_SANITIZE_WARMUP", int, 3,
        "Steps the retrace sanitizer treats as warmup before a fresh "
        "fused-step trace signature becomes an error (shape buckets and "
        "donation/fold config changes legitimately retrace early).",
        section="Runtime sanitizers")
declare("MXNET_TPU_WATCHDOG_S", float, 120.0,
        "Deadlock-watchdog stall threshold in seconds: when the "
        "`deadlock` sanitizer is armed and the step counter makes no "
        "progress for this long, the watchdog counts "
        "`sanitizer.trips.deadlock` and dumps all-thread stacks "
        "through the flight recorder (one dump per stall, re-armed "
        "when progress resumes).",
        section="Runtime sanitizers")
declare("MXNET_TPU_WATCHDOG_INTERVAL", float, 5.0,
        "Seconds between deadlock-watchdog polls of the progress "
        "signal.",
        section="Runtime sanitizers")

declare("MXNET_TPU_STRICT_FEED_GATE", bool, False,
        "Make the feed-the-chip test enforce the absolute host-feed-rate "
        "bar (nightly boxes); unset, the bar is reported but only the "
        "relative cached-vs-JPEG ratio is enforced.", section=_B)

declare("MXNET_TPU_TELEMETRY", bool, False,
        "Enable the framework-wide metric registry "
        "(`mxnet_tpu.telemetry`): engine push/dispatch counters and "
        "queue-wait histograms, io batch/prefetch-stall/decode-cache "
        "metrics, executor forward/backward and JIT cache-hit counters, "
        "kvstore op and byte counters, host-side spans. Off by default; "
        "the disabled path is one module-flag check per call site (no "
        "locks, no allocation). `telemetry.enable()` does the same at "
        "runtime.", section="Telemetry")
declare("MXNET_TPU_TELEMETRY_SPAN_CAP", int, 8192,
        "Bound on the buffered host-span ring; oldest spans are dropped "
        "first.", section="Telemetry")
declare("MXNET_TPU_TELEMETRY_FSYNC", bool, False,
        "fsync after every `telemetry.dump_jsonl` record. The append "
        "itself is already crash-safe (one `os.write` on an `O_APPEND` "
        "fd); the fsync is for machines where losing the last "
        "OS-buffered lines to a power cut matters more than a syscall "
        "per step.", section="Telemetry")

_T = "Tracing / flight recorder (all require telemetry enabled)"
declare("MXNET_TPU_METRICS_PORT", str, "",
        "Start the live metrics server on this port at `fit()` "
        "entry: Prometheus text format at `/metrics` (every sample "
        "labeled `rank=\"N\"`), liveness JSON at `/healthz`. Port `0` "
        "binds an ephemeral port (tests). Unset: no server thread.",
        section=_T)
declare("MXNET_TPU_TRACE_ON_ANOMALY", bool, False,
        "Anomaly events (slow step, steady-state recompile, "
        "input-stalled step) auto-start a short XLA trace window while "
        "the evidence is still happening.", section=_T)
declare("MXNET_TPU_TRACE_DIR", str, "",
        "Where anomaly trace windows are written (default "
        "`$TMPDIR/mxnet_tpu_anomaly_trace/step<N>_<type>`).", section=_T)
declare("MXNET_TPU_TRACE_WINDOW", int, 8,
        "Steps an anomaly-triggered capture stays open.", section=_T)
declare("MXNET_TPU_TRACE_COOLDOWN", float, 300.0,
        "Seconds between anomaly-triggered captures; triggers inside the "
        "cooldown are counted (`tracing.auto_trace_suppressed`) but not "
        "traced.", section=_T)
declare("MXNET_TPU_TRACE_RING", int, 512,
        "Per-step records kept in the step-trace ring.", section=_T)
declare("MXNET_TPU_TRACE_EVENT_COOLDOWN", int, 10,
        "Minimum steps between two anomaly events of the same type, "
        "bounding event spam from a persistently degraded run.",
        section=_T)
declare("MXNET_TPU_FLIGHT_RECORDER", bool, False,
        "Install the crash-dump hooks at `fit()` entry: unhandled "
        "exception, SIGTERM (dump then terminate normally) and SIGUSR1 "
        "(dump and keep running) write the last-N step records, "
        "all-thread stacks and a telemetry snapshot into the crash "
        "directory. See \"Interpreting step traces\" in "
        "`performance.md`.", section=_T)
declare("MXNET_TPU_CRASH_DIR", str, "",
        "Where flight-recorder dumps land (default "
        "`$TMPDIR/mxnet_tpu_crash`).", section=_T)

_X = "Device observability (xprof)"
declare("MXNET_TPU_XPROF_OPS", bool, True,
        "With telemetry on, every step-path jit compile (fused step, "
        "executor fwd+bwd, metric folds, kvstore reduce) goes through "
        "the compile registry (`mxnet_tpu.xprof`; no switch of its own) "
        "and each recorded executable's optimized HLO is parsed into the "
        "conv/dot/fusion/collective/transpose/elementwise FLOP+bytes "
        "breakdown (`trace_report.py --view ops`) and, the fused "
        "step's, into the census of its instructions by phase "
        "(`compile.fused_step.census.*`). Set to 0 to skip "
        "reading the text of very large modules; compile timing, "
        "`cost_analysis` and `memory_analysis` still record.",
        section=_X)
declare("MXNET_TPU_XPROF_PREFLIGHT", bool, True,
        "Pre-flight OOM check: when the device reports an HBM limit, "
        "a recorded executable whose `memory_analysis` footprint cannot fit "
        "raises before the first dispatch instead of OOM-ing minutes "
        "into a run. No-op where no limit is known (CPU).", section=_X)
declare("MXNET_TPU_XPROF_RECORDS", int, 256,
        "Bound on the compile registry ring; oldest CompileRecords are "
        "dropped first (per-site summaries keep their totals).",
        section=_X)

_S = "Serving"
declare("MXNET_TPU_SERVE_PORT", str, "",
        "Start the serving-tier metrics/health server on this port when "
        "an `InferenceServer` comes up (same endpoints as "
        "`MXNET_TPU_METRICS_PORT`: `/metrics`, `/healthz`). Port `0` "
        "binds an ephemeral port (tests). Unset: reuse a server already "
        "started via `MXNET_TPU_METRICS_PORT`, else none.", section=_S)
declare("MXNET_TPU_SERVE_MAX_BATCH", int, 64,
        "Upper bound on how many in-flight requests the continuous "
        "batcher coalesces into one `fused_infer` dispatch; also the "
        "top rung of the padded bucket ladder. Under a `dp` mesh it is "
        "rounded up to a multiple of the mesh size so every bucket "
        "shards evenly.", section=_S)
declare("MXNET_TPU_SERVE_MAX_WAIT_MS", float, 2.0,
        "How long the batcher holds an incomplete batch open for more "
        "arrivals before dispatching what it has. Larger values raise "
        "occupancy (throughput) and p50/p99 latency together; see the "
        "\"Serving\" section of `docs/performance.md` for the "
        "tradeoff.", section=_S)
declare("MXNET_TPU_SERVE_BUCKETS", str, "",
        "Comma-separated padded batch-size ladder (e.g. `1,2,4,8,16`). "
        "Every dispatched batch is padded up to the next rung so mixed "
        "request rates compile at most `len(buckets)` executables, "
        "ever. Unset: powers of two from 1 (or the mesh size) up to "
        "`MXNET_TPU_SERVE_MAX_BATCH`.", section=_S)
declare("MXNET_TPU_SERVE_SLO_MS", float, 0.0,
        "Per-request latency SLO in milliseconds. When the observed "
        "p99 over the sliding SLO window exceeds it, `/healthz` flips "
        "to `degraded` (HTTP 503) and a `slow_request` anomaly fires "
        "through the step-trace detectors. `0` disables SLO "
        "enforcement (latency is still measured).", section=_S)
declare("MXNET_TPU_SERVE_ADAPTIVE", bool, True,
        "Adaptive deadline-aware scheduling: a closed-loop controller "
        "replaces the fixed `MXNET_TPU_SERVE_MAX_WAIT_MS` coalescing "
        "window, widening it while the sliding-window p99 has headroom "
        "against `MXNET_TPU_SERVE_SLO_MS` (filling bigger buckets) and "
        "collapsing it near breach; dispatch is earliest-deadline-"
        "first with overload shedding. Needs a nonzero SLO to close "
        "the loop on — without one the static window applies "
        "regardless. Set to 0 to pin the wait manually.", section=_S)
declare("MXNET_TPU_SERVE_DEADLINE_MS", float, 0.0,
        "Default per-request deadline for the interactive lane when "
        "the caller does not pass `deadline_ms`. `0`: use the SLO "
        "(`MXNET_TPU_SERVE_SLO_MS`) when the adaptive scheduler is "
        "active, else no implicit deadline. Deadlines drive EDF "
        "dispatch order, the slack-triggered early dispatch, and "
        "which requests overload shedding may drop.", section=_S)
declare("MXNET_TPU_SERVE_BATCH_DEADLINE_MS", float, 0.0,
        "Default per-request deadline for the `batch` priority lane. "
        "`0`: 4x the interactive default. Batch-lane requests ride "
        "along in whatever bucket capacity the interactive lane "
        "leaves free and are the first shed under overload.",
        section=_S)
declare("MXNET_TPU_SERVE_TP", int, 0,
        "Tensor-parallel degree for an `InferenceServer`: the device "
        "group is refactored into a `(dp, tp)` mesh and each param is "
        "sharded along its largest `tp`-divisible dimension "
        "(replicated when none divides), so one model can span chips "
        "whose individual HBM it exceeds. Activations reshard "
        "in-graph — every batch is still exactly one XLA dispatch. "
        "Must divide the device-group size. `0`/`1`: no tensor "
        "sharding (the `dp`-replicated default).", section=_S)
declare("MXNET_TPU_REFRESH_DELTA", bool, True,
        "Delta-aware weight streaming for `refresh_params`: incoming "
        "host params are diffed per-param (sha256, the PR-11 snapshot "
        "manifest digests) against the resident pack and only changed "
        "shards cross the PCIe/ICI boundary. `infer.refresh_bytes` / "
        "`infer.refresh_skipped` report the savings. Set to 0 to "
        "force every refresh to move the full pack.", section=_S)

_F = "Fleet / fault injection"
declare("MXNET_TPU_FLEET_REPLICAS", int, 2,
        "Default replica count for a `fleet.FleetRouter` when the "
        "caller does not pass `n_replicas`. Autoscaling (when enabled) "
        "moves the live count between `MXNET_TPU_FLEET_MIN_REPLICAS` "
        "and `MXNET_TPU_FLEET_MAX_REPLICAS`.", section=_F)
declare("MXNET_TPU_FLEET_MIN_REPLICAS", int, 1,
        "Lower bound the fleet autoscaler will drain down to when every "
        "replica has been healthy for the scale-down patience window.",
        section=_F)
declare("MXNET_TPU_FLEET_MAX_REPLICAS", int, 4,
        "Upper bound the fleet autoscaler will grow to while replicas "
        "report a degraded `/healthz` (SLO probe failing).", section=_F)
declare("MXNET_TPU_FLEET_DEADLINE_MS", float, 2000.0,
        "Total per-request deadline budget across every retry and "
        "hedge the router makes. Attempt timeouts, backoff sleeps and "
        "hedge waits are all clamped to the remaining budget, so the "
        "caller never waits longer than this.", section=_F)
declare("MXNET_TPU_FLEET_ATTEMPT_TIMEOUT_MS", float, 500.0,
        "Per-attempt timeout: how long the router waits on one replica "
        "before counting the attempt failed and retrying elsewhere "
        "(clamped to the remaining deadline budget).", section=_F)
declare("MXNET_TPU_FLEET_RETRIES", int, 4,
        "Maximum attempts per request (first try + retries). Each "
        "failed attempt records a breaker failure on its replica and "
        "backs off exponentially with jitter before the next.",
        section=_F)
declare("MXNET_TPU_FLEET_BACKOFF_MS", float, 5.0,
        "Base of the exponential retry backoff: attempt `k` sleeps "
        "uniformly in `[base*2^k/2, base*2^k)` ms (full jitter halves "
        "synchronized retry storms), clamped to the remaining deadline "
        "budget.", section=_F)
declare("MXNET_TPU_FLEET_HEDGE", bool, False,
        "Tail-latency hedging: when an attempt is still pending at the "
        "router's observed p95, send a duplicate (same request-id, so "
        "the replica tier dedupes) to a second replica and take "
        "whichever answers first; the loser is abandoned and counted "
        "(`fleet.hedges`, `fleet.hedge_wins`).", section=_F)
declare("MXNET_TPU_FLEET_BREAKER_FAILS", int, 3,
        "Consecutive failures that trip a replica's circuit breaker "
        "from closed to open (load sheds to healthy peers).", section=_F)
declare("MXNET_TPU_FLEET_BREAKER_COOLDOWN_MS", float, 500.0,
        "How long an open breaker sheds load before letting one "
        "half-open probe request through; the probe's success closes "
        "the breaker, its failure re-opens it for another cooldown.",
        section=_F)
declare("MXNET_TPU_FAULTS", str, "",
        "Arm the typed fault-injection registry (`mxnet_tpu/faults.py`) "
        "with a comma list of `name` or `name:rate` entries, rate in "
        "[0,1] (default 1). Names: `replica_crash`, `slow_replica`, "
        "`drop_response`, `torn_swap`, `net_drop`, `net_partition`, "
        "`net_reorder`, `net_slow`; anything else fails fast at parse "
        "with the full valid-name list in the error. Unset: injection "
        "code is a single None-check in the hot path.", section=_F)
declare("MXNET_TPU_FAULTS_SEED", int, 0,
        "Seed for the fault plan's RNG: every injection decision draws "
        "from one seeded stream, so a chaos run replays bit-identically.",
        section=_F)
declare("MXNET_TPU_FAULT_SLOW_MS", float, 50.0,
        "Injected latency (ms) each time a `slow_replica` fault fires "
        "in the batcher's dispatch path, or a `net_slow` fault fires "
        "in the netwire send path.", section=_F)

_W = "Netwire / socket transport"
declare("MXNET_TPU_WIRE_POOL", int, 2,
        "Persistent connections per peer in a `netwire.WireClient` "
        "pool. Requests are multiplexed by message id and round-robin "
        "over the pool, so N is also the per-peer request concurrency "
        "a socket replica serves (each connection has one server-side "
        "reader). 2-4 covers a loopback fleet; raise it for "
        "high-fan-in cross-host peers.", section=_W)
declare("MXNET_TPU_WIRE_MAX_FRAME_MB", int, 4096,
        "Refuse any frame whose metadata or body length field exceeds "
        "this many MiB (default 4096 = 4 GiB) BEFORE allocating: a "
        "corrupt or hostile length prefix must not OOM the reader. "
        "Raising it past 4096 also requires peers new enough to parse "
        "64-bit body lengths (all WIRE_VERSION >= 1 peers do).",
        section=_W)
declare("MXNET_TPU_WIRE_CONNECT_TIMEOUT_MS", float, 2000.0,
        "TCP connect timeout for each `WireClient` pool slot; a peer "
        "that cannot be reached within it fails the attempt with "
        "`WirePeerLost` (the router's retry budget decides what "
        "happens next).", section=_W)
declare("MXNET_TPU_WIRE_BACKPRESSURE_MS", float, 20.0,
        "A frame send that blocks longer than this (socket buffer "
        "full = TCP backpressure) counts `wire.backpressure_stalls` "
        "and lands in the `wire.backpressure_stall_ms` histogram — "
        "the queue-depth signal that inflates rtt and feeds the "
        "router's hedge/breaker machinery.", section=_W)
declare("MXNET_TPU_NETFEED_DEPTH", int, 2,
        "Outstanding batch requests a `NetFeedIter` keeps in flight "
        "to its decode host (credit-based pipelining). Depth D means "
        "the decode host is always D batches ahead of the training "
        "loop; 2-4 hides loopback/LAN rtt completely (io.feed_stall_ms "
        "p99 ~ 0).", section=_W)
declare("MXNET_TPU_NETFEED_TIMEOUT_S", float, 30.0,
        "Per-batch reply deadline for `NetFeedIter.next()`: a decode "
        "host that cannot produce a batch within it fails the epoch "
        "with a named `WireTimeout` instead of wedging the training "
        "loop.", section=_W)

_D = "Distributed request tracing (dtrace)"
declare("MXNET_TPU_DTRACE", bool, False,
        "Arm the distributed request tracer (`mxnet_tpu/dtrace.py`): "
        "the fleet router opens a 128-bit root span per request, the "
        "trace context rides the subprocess wire envelope, and replica "
        "schedulers emit the queue/sched_idle/h2d/dispatch/d2h "
        "decomposition as child spans returned (clock-aligned) at "
        "reply time. Unset: the hot path is a single module-global "
        "None check (the `MXNET_TPU_FAULTS` idiom).", section=_D)
declare("MXNET_TPU_DTRACE_SAMPLE", int, 0,
        "Head-sampled keep floor for the tail-based sampler: keep "
        "every Nth trace even when nothing went wrong (errored, shed, "
        "SLO-breaching and hedged requests are always kept). `0` "
        "disables the floor — only tail-worthy trees survive "
        "root-finish.", section=_D)
declare("MXNET_TPU_DTRACE_BUFFER", int, 256,
        "Bound on concurrently in-flight trace trees per process. A "
        "request arriving with the buffer full goes untraced "
        "(`dtrace.overflow`) instead of growing the buffer.",
        section=_D)
declare("MXNET_TPU_DTRACE_KEEP", int, 64,
        "Finished kept traces retained for export (oldest evicted "
        "first); `dtrace.write_chrome_trace` and the trace_report "
        "waterfall read these.", section=_D)

_C = "Checkpointing"
declare("MXNET_TPU_CKPT_DIR", str, "",
        "Directory for step-granularity full-state training snapshots "
        "(params, optimizer state, metric accumulators, data cursor, "
        "RNG keys — see `mxnet_tpu/checkpoint.py`). Setting it arms "
        "the checkpoint manager inside `Module.fit`: periodic saves at "
        "`MXNET_TPU_CKPT_EVERY_N_STEPS`, a SIGTERM checkpoint-then-exit "
        "grace path, and automatic resume from the newest valid "
        "snapshot at the next fit() (`MXNET_TPU_CKPT_RESUME`). Unset "
        "disables all of it.", section=_C)
declare("MXNET_TPU_CKPT_EVERY_N_STEPS", int, 0,
        "Save a full-state snapshot every N training steps (batches). "
        "`0` disables periodic saves — with `MXNET_TPU_CKPT_DIR` set "
        "the SIGTERM grace path still writes a final snapshot on "
        "preemption. See docs/performance.md (\"Surviving "
        "preemption\") for cadence-vs-step-cost guidance.", section=_C)
declare("MXNET_TPU_CKPT_KEEP", int, 2,
        "How many snapshots to retain in `MXNET_TPU_CKPT_DIR`; older "
        "ones are pruned after each successful save. Keep >= 2 so a "
        "write torn by the preemption itself always leaves a loadable "
        "previous snapshot behind.", section=_C)
declare("MXNET_TPU_CKPT_RESUME", bool, True,
        "Auto-resume: when `MXNET_TPU_CKPT_DIR` holds a valid snapshot, "
        "`Module.fit` restores it (onto the *current* device mesh — a "
        "different dp count re-shards, it does not retrace) and "
        "continues from the saved step. `0` trains from scratch while "
        "still saving snapshots.", section=_C)
declare("MXNET_TPU_CKPT_GRACE_S", float, 25.0,
        "Deadline budget (seconds) for the SIGTERM grace save: the "
        "preemption hook abandons a snapshot whose device fetch + "
        "serialize phases exceed the budget rather than start a write "
        "it cannot finish (`ckpt.preempt_abandoned`); the previous "
        "snapshot stays valid either way.", section=_C)

declare("MXNET_TPU_NO_NATIVE", bool, False,
        "Disable the C++ runtime library (pure-Python recordio + engines "
        "only).", section="Native library / Pallas")
declare("MXNET_TPU_NO_PALLAS", bool, False,
        "Hard-disable all Pallas usage: every operator that chooses a "
        "kernel from its shapes keeps its XLA body.",
        section="Native library / Pallas")

_OW = "Obswatch / fleet federation"
declare("MXNET_TPU_OBSWATCH_INTERVAL_MS", float, 1000.0,
        "Scrape interval for the obswatch background poller "
        "(`mxnet_tpu.obswatch.ObsWatch.start()`): every tick scrapes "
        "each replica's metrics+health, federates, and appends one "
        "rollup record to the time-series store. Manual `tick()` "
        "callers ignore it.", section=_OW)
declare("MXNET_TPU_OBSWATCH_DIR", str, "",
        "Directory for the obswatch durable time-series store "
        "(JSONL ring segments + manifest). Empty: `.obswatch/` under "
        "the working directory.", section=_OW)
declare("MXNET_TPU_OBSWATCH_SEG_RECORDS", int, 1024,
        "Records per time-series segment before the store rolls over "
        "to a new `segment-N.jsonl`.", section=_OW)
declare("MXNET_TPU_OBSWATCH_SEG_KEEP", int, 8,
        "Ring retention: segments kept after rollover; older segments "
        "are deleted, bounding the store at roughly "
        "SEG_KEEP x SEG_RECORDS records.", section=_OW)
declare("MXNET_TPU_OBSWATCH_SLO_TARGET", float, 0.99,
        "Fraction of requests that must meet the latency SLO "
        "(`slo_ms`); 1 - target is the error budget the burn-rate "
        "monitor spends against.", section=_OW)
declare("MXNET_TPU_OBSWATCH_FAST_S", float, 300.0,
        "Fast burn-rate window (seconds). The classic multi-window "
        "pair is 5 m fast / 1 h slow: the fast window catches a new "
        "burn quickly, the slow window keeps the alert from flapping.",
        section=_OW)
declare("MXNET_TPU_OBSWATCH_SLOW_S", float, 3600.0,
        "Slow burn-rate window (seconds); see "
        "MXNET_TPU_OBSWATCH_FAST_S.", section=_OW)
declare("MXNET_TPU_OBSWATCH_BURN", float, 14.4,
        "Burn-rate alert threshold: fire when BOTH windows burn error "
        "budget faster than this multiple of the sustainable rate "
        "(14.4x spends a 30-day budget in ~2 days). The alert stamps "
        "`slo_burn_alert` into the step record (FleetHealthDetector "
        "anomaly) and flips a registered /healthz probe.", section=_OW)

_NW = "Numerics observability (numwatch)"
declare("MXNET_TPU_NUMWATCH", bool, False,
        "Arm the in-graph numerics plane (`mxnet_tpu.numwatch`): "
        "per-tensor gradient/param/update stats fold into a small f32 "
        "stats pack INSIDE the donated fused jit (dispatches/step stays "
        "exactly 1.0) and are host-fetched only on the "
        "MXNET_TPU_NUMWATCH_EVERY_N cadence. Also armed implicitly when "
        "a pack-expressible `Monitor` is installed.", section=_NW)
declare("MXNET_TPU_NUMWATCH_EVERY_N", int, 50,
        "Host-fetch cadence (steps) for the stats pack. Each fetch is "
        "one small D2H copy inside an `intentional_transfer` window — "
        "no extra dispatch — that updates `numwatch.*` telemetry, the "
        "health ring, and the anomaly-detector inputs.", section=_NW)
declare("MXNET_TPU_NUMWATCH_GUARD", str, "",
        "Guarded-training auto-actions, comma-separated, off by "
        "default. `skip`: an in-graph select drops any update whose "
        "gradients contain NaN/Inf (params/opt-state/metric accs keep "
        "their step k-1 values, still one dispatch). `rollback`: on a "
        "fetch that sees nonfinite PARAMS, restore the last healthy "
        "snapshot through CheckpointManager (requires "
        "MXNET_TPU_CKPT_DIR or an explicitly bound manager). Both "
        "actions are counted (`numwatch.skipped_steps`, "
        "`numwatch.rollbacks`) and rate-limited.", section=_NW)
declare("MXNET_TPU_NUMWATCH_SPIKE_K", float, 3.0,
        "Loss-spike detector threshold: fire `loss_spike` when the "
        "fetched in-graph loss exceeds this multiple of its rolling "
        "median.", section=_NW)
declare("MXNET_TPU_NUMWATCH_EXPLODE_K", float, 10.0,
        "Grad-explosion detector threshold: fire `grad_explosion` when "
        "the fetched global gradient norm exceeds this multiple of its "
        "rolling median.", section=_NW)
declare("MXNET_TPU_NUMWATCH_DEAD_UW", float, 1e-9,
        "Dead-update detector threshold: fire `dead_update` when the "
        "largest per-tensor update-to-weight ratio falls below this "
        "while gradients are still nonzero (lr collapsed, optimizer "
        "state saturated, or a frozen graph).", section=_NW)
declare("MXNET_TPU_NUMWATCH_MAX_SKIPS", int, 100,
        "Rate limit for the `skip` guard: once the in-graph skip "
        "counter passes this many skipped steps, numwatch logs an "
        "error, counts `numwatch.skip_cap_exceeded`, and (when the "
        "rollback guard is armed) escalates to a rollback — endless "
        "silent skipping is never a steady state.", section=_NW)
declare("MXNET_TPU_NUMWATCH_ROLLBACK_COOLDOWN", int, 200,
        "Rate limit for the `rollback` guard: at least this many steps "
        "must pass between two rollbacks; a still-unhealthy model "
        "inside the cooldown raises instead of thrashing the "
        "snapshot store.", section=_NW)


# ---------------------------------------------------------------------------
# docs generation
# ---------------------------------------------------------------------------

DOC_BEGIN = ("<!-- BEGIN MXNET_TPU ENV REGISTRY "
             "(generated from mxnet_tpu/env.py; run "
             "`python tools/graftlint.py --write-env-docs`; do not edit "
             "by hand) -->")
DOC_END = "<!-- END MXNET_TPU ENV REGISTRY -->"


def _fmt_default(v: EnvVar) -> str:
    if v.type is bool:
        return "`1`" if v.default else "`0`"
    if v.type is str:
        return "unset" if v.default == "" else "`%s`" % v.default
    return "`%s`" % (v.default,)


def generate_docs() -> str:
    """The generated `MXNET_TPU_*` block of docs/env_vars.md: every
    declared variable, grouped by section, in declaration order."""
    out = [DOC_BEGIN, ""]
    for section in _SECTIONS:
        out.append("## %s" % section)
        out.append("")
        for v in _REGISTRY.values():
            if v.section != section:
                continue
            out.append("- `%s` (%s, default %s) — %s"
                       % (v.name, v.type.__name__, _fmt_default(v), v.doc))
        out.append("")
    out.append(DOC_END)
    return "\n".join(out)


def sync_docs(path: str, check: bool = False) -> bool:
    """Rewrite (or with ``check=True`` just verify) the generated block
    between :data:`DOC_BEGIN` / :data:`DOC_END` markers in ``path``.
    Returns True when the file already matched."""
    with open(path) as f:
        text = f.read()
    try:
        head, rest = text.split(DOC_BEGIN, 1)
        _, tail = rest.split(DOC_END, 1)
    except ValueError:
        raise ValueError("%s has no %r...%r markers" %
                         (path, DOC_BEGIN[:30], DOC_END))
    new = head + generate_docs() + tail
    if new == text:
        return True
    if check:
        return False
    with open(path, "w") as f:
        f.write(new)
    return False
