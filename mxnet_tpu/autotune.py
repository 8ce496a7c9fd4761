"""Closed-loop kernel/config autotuner over the xprof compile registry.

For a kernel *site* (a named decision point — ``conv_backward``,
``norm_act``, ``fused_step``) it enumerates a candidate space, compiles
each candidate through the same ``lower().compile()`` path ``xprof.jit``
measures, reads the CompileRegistry's cost/memory analysis to prune
candidates that are pre-flight OOM or roofline-hopeless *before spending
device time*, times the survivors in-process, and hands every candidate
— winners and losers, with prune reasons — to :func:`record`, which
appends them to the caller's .jsonl behind the :func:`validate` fence so
no physically impossible row ever lands. The winning config is persisted
to a per-(site, aval-signature, chip) cache that ``ops/nn.py`` and
``fused_step`` consult at *trace time*, so a tuned choice costs zero
extra dispatches per training step. Nothing in the repo runs a search:
:func:`run_smoke` is the entry, and no cell has timed its choices on the
chip (ROADMAP Design 8).

The search core (:func:`search`) takes injected ``compile_fn``/
``run_fn``/``clock`` so tests drive it off a fake registry with a fake
clock and assert determinism; the real builders live next to it.

Knobs: ``MXNET_TPU_AUTOTUNE`` turns cache consultation on,
``MXNET_TPU_AUTOTUNE_BUDGET_S`` bounds a search,
``MXNET_TPU_PALLAS_CONV`` force-enables the conv-backward kernels
without a cache entry (the pin/override path, docs/performance.md).
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from . import env as _env
from .base import MXNetError

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_FILE = os.path.join(_ROOT, ".autotune_cache.json")

# model FLOPs of one ResNet-50 training image at 224x224 (forward x 3)
RESNET50_TRAIN_GFLOPS_PER_IMG = 4.089 * 3


def enabled() -> bool:
    return bool(_env.get("MXNET_TPU_AUTOTUNE"))


def budget_s() -> float:
    return float(_env.get("MXNET_TPU_AUTOTUNE_BUDGET_S"))


# ---------------------------------------------------------------------------
# search core (injectable: tested off a fake registry + fake clock)
# ---------------------------------------------------------------------------

def search(site: str, candidates: List[dict],
           compile_fn: Callable[[dict], dict],
           run_fn: Callable[[dict], float], *,
           budget_s: Optional[float] = None,
           limit_bytes: Optional[int] = None,
           peak_tflops: Optional[float] = None,
           repeats: int = 3,
           clock: Callable[[], float] = time.perf_counter):
    """Measure a candidate space for one site; deterministic in the
    candidate order (ties keep the earliest).

    ``candidates`` is ``[{"name": ..., "config": {...}}, ...]`` with the
    DEFAULT config first — it is always measured, so later candidates
    can be roofline-pruned against a real time. ``compile_fn(cand)``
    returns registry facts (``flops``, ``peak_bytes``,
    ``compile_time_s``; raise :class:`MXNetError` for inapplicable
    candidates). ``run_fn(cand)`` returns one fenced step time in
    seconds; the best of ``repeats`` runs is kept.

    Prunes, in order: inapplicable (compile raised), pre-flight OOM
    (``peak_bytes`` over ``limit_bytes``), roofline-hopeless (the
    executable's FLOP floor at ``peak_tflops`` already exceeds the best
    measured time), and budget exhaustion. Every candidate yields a
    row; pruned rows carry the reason instead of a time. Returns
    ``(summary, rows)``.
    """
    t0 = clock()
    rows: List[dict] = []
    best = None          # (step_ms, index, cand, info)
    default_ms = None
    n_pre = n_roof = n_budget = n_inapplicable = 0

    for idx, cand in enumerate(candidates):
        row = {"experiment": "autotune:%s:%s" % (site, cand["name"]),
               "site": site, "candidate": cand["name"],
               "config": cand.get("config", {})}
        if budget_s is not None and idx > 0 and clock() - t0 > budget_s:
            row["pruned"] = ("budget exhausted (%.1fs)" % budget_s)
            n_budget += 1
            rows.append(row)
            continue
        try:
            info = compile_fn(cand) or {}
        except MXNetError as e:
            row["pruned"] = str(e)
            n_inapplicable += 1
            rows.append(row)
            continue
        if info.get("compile_time_s") is not None:
            row["compile_time_s"] = round(float(info["compile_time_s"]), 4)
        if info.get("flops"):
            row["flops_per_step"] = float(info["flops"])
        if info.get("peak_bytes"):
            row["peak_bytes"] = int(info["peak_bytes"])
        if (limit_bytes and info.get("peak_bytes")
                and info["peak_bytes"] > limit_bytes):
            row["pruned"] = ("pre-flight OOM: needs %d bytes at peak, "
                             "device limit %d" % (info["peak_bytes"],
                                                  limit_bytes))
            n_pre += 1
            rows.append(row)
            continue
        if peak_tflops and info.get("flops") and best is not None:
            floor_ms = float(info["flops"]) / (peak_tflops * 1e9)
            if floor_ms >= best[0]:
                row["pruned"] = ("roofline-hopeless: FLOP floor %.3f ms "
                                 ">= best measured %.3f ms"
                                 % (floor_ms, best[0]))
                n_roof += 1
                rows.append(row)
                continue
        step_s = min(run_fn(cand) for _ in range(max(1, repeats)))
        step_ms = step_s * 1e3
        row["step_time_ms"] = round(step_ms, 4)
        if peak_tflops and info.get("flops"):
            achieved = float(info["flops"]) / step_s
            row["analytic_mfu_pct"] = round(
                100.0 * achieved / (peak_tflops * 1e12), 2)
        if idx == 0:
            default_ms = step_ms
        if best is None or step_ms < best[0]:
            best = (step_ms, idx, cand, info)
        rows.append(row)

    result = {"site": site, "candidates": len(candidates),
              "measured": sum(1 for r in rows if "step_time_ms" in r),
              "pruned_preflight": n_pre, "pruned_roofline": n_roof,
              "pruned_inapplicable": n_inapplicable,
              "pruned_budget": n_budget,
              "default_ms": round(default_ms, 4) if default_ms else None,
              "best": None, "speedup_vs_default": None,
              "search_time_s": round(clock() - t0, 3)}
    if best is not None:
        step_ms, idx, cand, _info = best
        result["best"] = {"candidate": cand["name"],
                          "config": cand.get("config", {}),
                          "step_time_ms": round(step_ms, 4)}
        result["non_default"] = idx != 0
        if default_ms:
            result["speedup_vs_default"] = round(default_ms / step_ms, 3)
        for r in rows:
            r["best"] = r["candidate"] == cand["name"]
    return result, rows


# ---------------------------------------------------------------------------
# validate-fenced JSONL recording
# ---------------------------------------------------------------------------

def _chip_peak(kind):
    """Peak TFLOP/s from xprof's one table; None for the CPU or a chip
    the table does not hold (no floor can be computed then)."""
    from .xprof import chip_peak_tflops

    try:
        return chip_peak_tflops(kind)
    except MXNetError:
        return None


def validate(result):
    """Physical-plausibility gate for a measurement row. Returns None
    when the row could be real, else a reason string.

    Two invariants no correct measurement can break: model FLOP
    utilization cannot exceed the chip's peak (mfu_pct <= 100), and a
    step cannot finish faster than its FLOPs take at that peak. Rows
    that break either measured dispatch latency, not training."""
    mfu = result.get("mfu_pct")
    if mfu is not None and mfu > 100.0:
        return "mfu_pct %.1f exceeds 100%% of chip peak" % mfu
    step_ms = result.get("step_time_ms")
    peak = _chip_peak(result.get("chip", "")) if step_ms else None
    if not peak:
        return None
    # rows measured through the xprof registry carry the compiled
    # executable's true FLOP count: the tightest floor, valid for
    # every geometry
    flops = result.get("flops_per_step")
    if flops:
        floor_ms = flops / (peak * 1e9)
        if step_ms < floor_ms:
            return ("step_time_ms %.2f below executable FLOP floor "
                    "%.2f ms (%.1f GFLOP/step at %.0f peak TFLOPS)"
                    % (step_ms, floor_ms, flops / 1e9, peak))
    # a 224px ResNet-50 row is also held to the model's own count
    batch = result.get("batch")
    if batch and result.get("image", 0) >= 224:
        floor_ms = batch * RESNET50_TRAIN_GFLOPS_PER_IMG / peak
        if step_ms < floor_ms:
            return ("step_time_ms %.2f below analytic floor %.2f ms "
                    "(batch %d ResNet-50 train at %.0f peak TFLOPS)"
                    % (step_ms, floor_ms, batch, peak))
    return None


def record(rows: List[dict], path: str,
           chip: Optional[str] = None) -> dict:
    """Append search rows to the .jsonl at ``path`` behind the
    validate() fence: rows the gate rejects are REFUSED (returned with
    the reason), never written — the results file only ever gains
    ``valid: true`` rows."""
    written, refused = [], []
    for row in rows:
        row = dict(row)
        if chip and "chip" not in row:
            row["chip"] = chip
        reason = validate(row)
        if reason:
            row["refused"] = reason
            refused.append(row)
            continue
        row["valid"] = True
        written.append(row)
    if written:
        with open(path, "a") as f:
            for row in written:
                f.write(json.dumps(row, sort_keys=True) + "\n")
    return {"written": len(written), "refused": len(refused),
            "refused_rows": refused}


# ---------------------------------------------------------------------------
# best-config cache: per (site, aval signature, chip), consulted at
# trace time by ops/nn.py and fused_step
# ---------------------------------------------------------------------------

_cache_lock = threading.Lock()
_cache_memo: Optional[dict] = None


def _key(site: str, sig: str, chip: str) -> str:
    return "%s|%s|%s" % (site, sig, chip)


def load_cache(path: Optional[str] = None, refresh: bool = False) -> dict:
    global _cache_memo
    path = path or CACHE_FILE
    with _cache_lock:
        if _cache_memo is not None and not refresh \
                and path == CACHE_FILE:
            return _cache_memo
        try:
            with open(path) as f:
                cache = json.load(f)
            if not isinstance(cache.get("entries"), dict):
                cache = {"version": 1, "entries": {}}
        except (OSError, ValueError):
            cache = {"version": 1, "entries": {}}
        if path == CACHE_FILE:
            _cache_memo = cache
        return cache


def save_best(site: str, config: dict, *, sig: str = "*",
              chip: str = "*", candidate: Optional[str] = None,
              step_time_ms: Optional[float] = None,
              path: Optional[str] = None) -> None:
    """Persist a winning config (atomic replace — a crash leaves the
    old cache intact, same guarantee as checkpoints)."""
    from .checkpoint import atomic_writer

    global _cache_memo
    path = path or CACHE_FILE
    cache = load_cache(path, refresh=True)
    entry = {"config": dict(config), "candidate": candidate,
             "step_time_ms": step_time_ms, "ts": round(time.time(), 3)}
    with _cache_lock:
        cache["entries"][_key(site, sig, chip)] = entry
        with atomic_writer(path, mode="w") as f:
            json.dump(cache, f, indent=2, sort_keys=True)
            f.write("\n")
        if path == CACHE_FILE:
            _cache_memo = cache


def best_config(site: str, sig: Optional[str] = None,
                chip: Optional[str] = None,
                path: Optional[str] = None) -> Optional[dict]:
    """Most-specific cache hit for a site: exact (sig, chip) first,
    then sig-wildcard, chip-wildcard, both-wildcard."""
    entries = load_cache(path).get("entries", {})
    for s in ((sig, "*") if sig else ("*",)):
        for c in ((chip, "*") if chip else ("*",)):
            hit = entries.get(_key(site, s, c))
            if hit:
                return hit.get("config")
    return None


def aval_sig(shape, dtype) -> str:
    """Cache key fragment for one input aval, matching xprof's
    ``(shape)dtype`` rendering."""
    return "(%s)%s" % (",".join(str(d) for d in shape), str(dtype))


def _chip_kind() -> str:
    try:
        import jax
        return jax.devices()[0].device_kind
    except Exception:
        return "*"


# -- trace-time consumers ---------------------------------------------------

def conv_kernel_enabled(sig: Optional[str] = None,
                        chip: Optional[str] = None) -> bool:
    """Should Convolution route its backward through the Pallas
    dgrad/wgrad kernels? ``MXNET_TPU_PALLAS_CONV`` pins yes regardless
    of the cache (the chip-window override); otherwise the autotuner
    must be on AND the cache must hold a measured win for the
    ``conv_backward`` site. Pure trace-time: zero per-dispatch cost."""
    if _env.get("MXNET_TPU_PALLAS_CONV"):
        return True
    if not enabled():
        return False
    cfg = best_config("conv_backward", sig, chip or _chip_kind())
    return bool(cfg and cfg.get("kernel") == "pallas")


def conv_tiles(sig: Optional[str] = None,
               chip: Optional[str] = None) -> tuple:
    cfg = best_config("conv_backward", sig, chip or _chip_kind()) or {}
    tiles = cfg.get("tiles")
    return tuple(tiles) if tiles else (128, 128, 128)


def norm_block_rows(sig: Optional[str] = None,
                    chip: Optional[str] = None) -> Optional[int]:
    """Tuned ``block_rows`` for the fused norm+act kernel, or None when
    the autotuner is off / holds no measurement (caller keeps the XLA
    elementwise path)."""
    if not enabled():
        return None
    cfg = best_config("norm_act", sig, chip or _chip_kind())
    if not cfg:
        return None
    br = cfg.get("block_rows")
    return int(br) if br else None


_noted: set = set()


def note_build(site: str) -> Optional[dict]:
    """Build-time observability hook for jitted sites (fused_step):
    returns the applied best config and telemeters the consultation
    once per site. Called while tracing — never on the dispatch path."""
    if not enabled():
        return None
    cfg = best_config(site, chip=_chip_kind())
    if site not in _noted:
        _noted.add(site)
        try:
            from . import telemetry as _tel
            if _tel.enabled():
                _tel.inc("autotune.consulted")
                if cfg:
                    _tel.inc("autotune.applied")
        except Exception:
            pass
    return cfg


# ---------------------------------------------------------------------------
# real sites: compile through the registry, time with a fence
# ---------------------------------------------------------------------------

def _registry_tools(site: str, build_fn: Callable[[dict], tuple]):
    """(compile_fn, run_fn) pair for a real jax site. ``build_fn(cand)``
    returns ``(callable, args)``; the callable is jitted, compiled via
    the same ``lower().compile()`` path ``xprof.jit`` measures, and the
    executable + registry facts are cached per candidate name."""
    import jax

    from . import xprof as _xprof

    compiled_cache: Dict[str, Any] = {}

    def compile_fn(cand: dict) -> dict:
        fn, args = build_fn(cand)
        if fn is None:
            raise MXNetError("candidate %r not applicable to this shape"
                             % cand["name"])
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        dt = time.perf_counter() - t0
        rec = _xprof.record_compile("autotune.%s" % site, compiled, dt)
        compiled_cache[cand["name"]] = (compiled, args)
        return {"flops": rec.flops, "peak_bytes": rec.held_bytes,
                "bytes_accessed": rec.bytes_accessed,
                "compile_time_s": dt}

    def run_fn(cand: dict) -> float:
        compiled, args = compiled_cache[cand["name"]]
        jax.block_until_ready(compiled(*args))   # warm / fence
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        return time.perf_counter() - t0

    return compile_fn, run_fn


def norm_act_candidates() -> List[dict]:
    # default first: block_rows is the row-tile knob of fused_norm_act
    return [{"name": "rows%d" % r, "config": {"block_rows": r}}
            for r in (128, 256, 512)]


def conv_backward_candidates() -> List[dict]:
    return [
        {"name": "xla", "config": {"kernel": "xla"}},
        {"name": "pallas-128", "config": {"kernel": "pallas",
                                          "tiles": [128, 128, 128]}},
        {"name": "pallas-256", "config": {"kernel": "pallas",
                                          "tiles": [256, 128, 128]}},
    ]


def _norm_site(rows: int = 4096, cols: int = 128):
    import jax.numpy as jnp
    import numpy as np

    from .ops import pallas_kernels as pk

    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(rows, cols), jnp.float32)
    sc = jnp.asarray(rng.randn(cols) * 0.5 + 1.0, jnp.float32)
    sh = jnp.asarray(rng.randn(cols) * 0.1, jnp.float32)

    def build(cand):
        br = cand["config"]["block_rows"]
        if not pk.norm_act_applicable(x.shape, x.dtype, br):
            return None, None

        def fn(x, sc, sh):
            out = pk.fused_norm_act(x, sc, sh, act="relu", block_rows=br)
            return out.sum()
        return fn, (x, sc, sh)

    return build


def _conv_site(shape=(2, 128, 8, 8), wshape=(128, 128, 3, 3),
               stride=(1, 1), pad=(1, 1)):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .ops import pallas_kernels as pk

    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    w = jnp.asarray(rng.randn(*wshape) * 0.1, jnp.float32)

    def build(cand):
        cfg = cand["config"]

        if cfg["kernel"] == "xla":
            def loss(x, w):
                out = jax.lax.conv_general_dilated(
                    x, w, window_strides=stride,
                    padding=[(p, p) for p in pad],
                    dimension_numbers=("NCHW", "OIHW", "NCHW"),
                    preferred_element_type=jnp.float32)
                return (out * out).sum()
        else:
            tiles = tuple(cfg["tiles"])
            nhwc_shape = (shape[0], shape[2], shape[3], shape[1])
            if not pk.conv_backward_applicable(
                    nhwc_shape, wshape, stride, pad, (1, 1), 1, tiles):
                return None, None

            def loss(x, w):
                out = pk.conv2d(x, w, stride=stride, pad=pad,
                                tiles=tiles)
                return (out * out).sum()

        def fn(x, w):
            return jax.grad(loss, (0, 1))(x, w)
        return fn, (x, w)

    return build


def run_smoke(jsonl_path: str, budget: Optional[float] = None,
              cache_path: Optional[str] = None) -> dict:
    """A bounded search, callable by itself (nothing else runs it):
    tune the ``norm_act`` row tile and the ``conv_backward`` kernel
    choice on fixed smoke shapes, append every row that passes
    validate() to ``jsonl_path``, persist winners to the cache
    (``cache_path``, default :data:`CACHE_FILE`, what
    ``MXNET_TPU_AUTOTUNE=1`` reads), and return the search summary."""
    from . import xprof as _xprof

    budget = budget_s() if budget is None else budget
    chip = _chip_kind()
    limit = _xprof.device_memory_limit()
    peak = _xprof.chip_peak_tflops(chip)
    summary = {"chip": chip, "budget_s": budget, "sites": {},
               "rows_written": 0, "rows_refused": 0,
               "non_default_winner": False}

    sites = (
        ("norm_act", norm_act_candidates(), _norm_site()),
        ("conv_backward", conv_backward_candidates(), _conv_site()),
    )
    for site, cands, build in sites:
        compile_fn, run_fn = _registry_tools(site, build)
        result, rows = search(site, cands, compile_fn, run_fn,
                              budget_s=budget, limit_bytes=limit,
                              peak_tflops=peak)
        rec = record(rows, jsonl_path, chip=chip)
        summary["rows_written"] += rec["written"]
        summary["rows_refused"] += rec["refused"]
        if result["best"] is not None:
            save_best(site, result["best"]["config"],
                      chip=chip, candidate=result["best"]["candidate"],
                      step_time_ms=result["best"]["step_time_ms"],
                      path=cache_path)
            if result.get("non_default"):
                summary["non_default_winner"] = True
        summary["sites"][site] = result
    return summary
