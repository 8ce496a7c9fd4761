"""Device observability plane: compile analytics, per-op FLOP/memory
attribution, and HBM accounting.

Host-side observability (telemetry counters, StepTrace, lock
contention) tells you *when* a step was slow; this module tells you
what the *device* was doing. The reference framework's ``profiler.h``
layer attributed time to individual engine ops; the XLA-native
equivalent is compile-time analysis of the executables the step path
actually runs:

* **CompileRegistry** — every step-path jit (fused_step, the
  executor's fused fwd+bwd, metric folds, kvstore reduce) is routed
  through :func:`jit`, an AOT ``lower()``/``compile()`` wrapper that
  records compile wall-time, the argument-aval signature,
  ``cost_analysis()`` FLOPs / bytes-accessed and ``memory_analysis()``
  argument/output/temp/peak bytes into the ``compile.*`` telemetry
  namespace. A recompile carries a *retrace-cause diff* naming exactly
  which avals changed vs the previous signature — "(64,3,224,224)f32
  -> (32,3,224,224)f32 on batch.data" instead of "something retraced".
* **Op-category attribution** — :func:`hlo_op_breakdown` parses the
  compiled executable's optimized HLO into a conv / dot / fusion /
  collective / transpose / elementwise FLOP+bytes table whose category
  sums ARE the reported totals (exact by construction), so the
  measured-vs-analytic MFU gap is attributable to a specific category.
  :func:`analyze` adds analytic MFU, arithmetic intensity and a
  compute- vs bandwidth-bound classification from the chip's peak
  FLOPs and HBM bandwidth.
* **HBM accounting** — :class:`HbmWatermark` samples the live-buffer
  watermark per step (``device.memory_stats()`` on TPU,
  ``jax.live_arrays()`` fallback on CPU), feeds the
  ``hbm.headroom_bytes`` gauge the MetricsServer exports, and
  :func:`preflight_check` refuses a config whose ``memory_analysis``
  peak cannot fit before a single step runs.

Everything except profiler trace capture works on CPU, so tier-1
exercises the whole plane (``tests/test_xprof.py``).

Design note: jax's AOT path does NOT populate the jit dispatch cache,
so a naive "lower+compile to measure, then call the jit" pays every
compile twice. The wrapper therefore *keeps* the AOT executable it
measured and dispatches through it — instrumentation adds zero extra
compiles and zero extra dispatches (regression-tested against
``dispatches_per_step``).
"""
from __future__ import annotations

import logging
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from . import env as _env
from . import telemetry as _tel
from .base import MXNetError

_log = logging.getLogger(__name__)

__all__ = [
    "enabled", "enable", "disable", "reset", "jit", "record_compile",
    "records", "summary", "last_retrace_cause", "hlo_op_breakdown",
    "analyze", "chip_peaks", "chip_peak_tflops", "chip_hbm_gbps",
    "CHIP_PEAKS", "hbm_stats",
    "HbmWatermark", "preflight_check", "device_memory_limit",
    "CompileRecord", "CATEGORIES",
]

# ---------------------------------------------------------------------------
# enablement
# ---------------------------------------------------------------------------

_override: Optional[bool] = None


def enabled() -> bool:
    """Master switch: ``MXNET_TPU_XPROF`` or a runtime enable()."""
    if _override is not None:
        return _override
    return bool(_env.get("MXNET_TPU_XPROF"))


def enable():
    global _override
    _override = True


def disable():
    global _override
    _override = False


# ---------------------------------------------------------------------------
# compile registry
# ---------------------------------------------------------------------------

class CompileRecord:
    """One measured ``lower()``/``compile()`` of a step-path site."""

    __slots__ = ("site", "seq", "compile_time_s", "signature", "flops",
                 "bytes_accessed", "argument_bytes", "output_bytes",
                 "temp_bytes", "peak_bytes", "generated_code_bytes",
                 "op_breakdown", "retrace_cause", "num_devices", "ts")

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))

    def to_dict(self) -> dict:
        d = {k: getattr(self, k) for k in self.__slots__}
        d["signature"] = [[n, list(a[0]), a[1]]
                          + ([a[3]] if len(a) > 3 and a[3] else [])
                          for n, a in (self.signature or ())]
        return d


_lock = threading.RLock()
_records: List[CompileRecord] = []
_sites: Dict[str, dict] = {}
_last_cause: Optional[str] = None
_seq = 0


def reset():
    """Clear recorded compiles and per-site state (not the enable
    override — tests pair enable()/disable() explicitly)."""
    global _last_cause, _seq
    with _lock:
        del _records[:]
        _sites.clear()
        _last_cause = None
        _seq = 0


def records() -> List[CompileRecord]:
    with _lock:
        return list(_records)


def last_retrace_cause() -> Optional[str]:
    """The most recent recompile's aval diff (None before any retrace);
    the RecompileDetector attaches this to its anomaly events."""
    return _last_cause


# -- argument signatures ----------------------------------------------------

def _sharding_fp(x) -> Optional[str]:
    """Stable placement fingerprint for a device array, or None for
    host arrays. Part of the AOT-cache key: two calls with identical
    shapes but different shardings (a server re-bound across mesh
    factorings) must NOT share an executable — dispatching one
    compiled for the old placement silently computes on wrong layouts.
    """
    sh = getattr(x, "sharding", None)
    if sh is None:
        return None
    spec = getattr(sh, "spec", None)
    mesh = getattr(sh, "mesh", None)
    if spec is not None and mesh is not None:
        axes = ",".join("%s=%d" % (a, int(mesh.shape[a]))
                        for a in mesh.axis_names)
        return "mesh(%s)%s" % (axes, spec)
    dev = getattr(sh, "_device", None)
    if dev is not None:
        return "dev(%s)" % (dev,)
    return type(sh).__name__


def _aval(x) -> tuple:
    shape = tuple(int(d) for d in getattr(x, "shape", ()) or ())
    dtype = str(getattr(x, "dtype", type(x).__name__))
    return (shape, dtype, bool(getattr(x, "weak_type", False)),
            _sharding_fp(x))


def _fmt_aval(a) -> str:
    shape, dtype = a[0], a[1]
    placed = a[3] if len(a) > 3 and a[3] else ""
    return "(%s)%s%s" % (",".join(str(d) for d in shape), dtype,
                         "@" + placed if placed else "")


def leaf_signature(args, arg_names=None) -> tuple:
    """((name, (shape, dtype, weak_type)), ...) over the flattened
    positional args. ``arg_names[i]`` labels arg i; a list/tuple entry
    names that argument's leaves individually (the fused step passes
    the executor's own arg names, so a diff says ``batch.data`` rather
    than ``arg1[0]``)."""
    import jax

    specs = []
    for i, a in enumerate(args):
        name = arg_names[i] if arg_names and i < len(arg_names) else None
        flat = jax.tree_util.tree_flatten_with_path(a)[0]
        for j, (kp, leaf) in enumerate(flat):
            if isinstance(name, (list, tuple)):
                label = (name[j] if j < len(name)
                         else "arg%d%s" % (i, jax.tree_util.keystr(kp)))
            elif name:
                label = name + jax.tree_util.keystr(kp)
            else:
                label = "arg%d%s" % (i, jax.tree_util.keystr(kp))
            specs.append((label, _aval(leaf)))
    return tuple(specs)


def diff_signatures(prev, cur) -> Optional[str]:
    """Human-readable retrace cause: which leaves' avals changed."""
    if prev is None or prev == cur:
        return None
    if len(prev) != len(cur):
        return ("argument tree changed: %d -> %d leaves"
                % (len(prev), len(cur)))
    changes = ["%s -> %s on %s" % (_fmt_aval(pa), _fmt_aval(ca), cn)
               for (_pn, pa), (cn, ca) in zip(prev, cur) if pa != ca]
    if not changes:
        return "argument names changed (same avals)"
    head = "; ".join(changes[:3])
    if len(changes) > 3:
        head += " (+%d more)" % (len(changes) - 3)
    return head


# -- executable analysis ----------------------------------------------------

def _cost_dict(compiled) -> dict:
    try:
        c = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    return dict(c) if c else {}


def _device_count(compiled) -> int:
    """Devices the executable was SPMD-partitioned over (1 for an
    unsharded step; the dp mesh size for the sharded fused step) — the
    compile-registry witness that GSPMD actually partitioned a site."""
    return len(compiled.runtime_executable().local_devices())


def _memory_dict(compiled) -> Optional[dict]:
    try:
        m = compiled.memory_analysis()
    except Exception:
        return None
    if isinstance(m, (list, tuple)):
        m = m[0] if m else None
    if m is None:
        return None
    out = {}
    for key, attr in (("argument_bytes", "argument_size_in_bytes"),
                      ("output_bytes", "output_size_in_bytes"),
                      ("temp_bytes", "temp_size_in_bytes"),
                      ("alias_bytes", "alias_size_in_bytes"),
                      ("generated_code_bytes",
                       "generated_code_size_in_bytes")):
        out[key] = int(getattr(m, attr, 0) or 0)
    # aliased (donated) buffers are counted once: they are argument
    # bytes XLA reuses for outputs, not extra live memory at peak
    out["peak_bytes"] = max(0, out["argument_bytes"] + out["output_bytes"]
                            + out["temp_bytes"]
                            + out["generated_code_bytes"]
                            - out["alias_bytes"])
    return out


def record_compile(site: str, compiled, compile_time_s: float,
                   signature: Optional[tuple] = None) -> CompileRecord:
    """Record one measured compile into the registry + ``compile.*``
    telemetry; computes the retrace-cause diff against the site's
    previous signature."""
    global _last_cause, _seq
    cost = _cost_dict(compiled)
    mem = _memory_dict(compiled) or {}
    breakdown = None
    if _env.get("MXNET_TPU_XPROF_OPS"):
        try:
            breakdown = hlo_op_breakdown(compiled.as_text())
        except Exception:
            breakdown = None
    flops = cost.get("flops")
    flops = float(flops) if flops else None
    if flops is None and breakdown:
        flops = float(sum(v["flops"] for v in breakdown.values()))
    ba = cost.get("bytes accessed")
    with _lock:
        st = _sites.setdefault(site, {"compiles": 0, "time_s": 0.0,
                                      "sig": None, "last": None})
        cause = diff_signatures(st["sig"], signature) \
            if signature is not None else None
        _seq += 1
        rec = CompileRecord(
            site=site, seq=_seq,
            compile_time_s=round(float(compile_time_s), 6),
            signature=signature, flops=flops,
            bytes_accessed=float(ba) if ba else None,
            argument_bytes=mem.get("argument_bytes"),
            output_bytes=mem.get("output_bytes"),
            temp_bytes=mem.get("temp_bytes"),
            peak_bytes=mem.get("peak_bytes"),
            generated_code_bytes=mem.get("generated_code_bytes"),
            op_breakdown=breakdown, retrace_cause=cause,
            num_devices=_device_count(compiled),
            ts=round(time.time(), 6))
        st["compiles"] += 1
        st["time_s"] += float(compile_time_s)
        st["sig"] = signature
        st["last"] = rec
        _records.append(rec)
        cap = int(_env.get("MXNET_TPU_XPROF_RECORDS"))
        if len(_records) > cap:
            del _records[:len(_records) - cap]
        if cause:
            _last_cause = "%s: %s" % (site, cause)
    if _tel.enabled():
        _tel.inc("compile.count")
        _tel.observe("compile.time_ms", compile_time_s * 1e3)
        if flops:
            _tel.inc("compile.flops", int(flops))
        if rec.peak_bytes:
            _tel.set_gauge("compile.peak_bytes", rec.peak_bytes)
    return rec


def summary() -> dict:
    """JSON-able registry summary (``tools/trace_report.py`` renders it)."""
    with _lock:
        sites = {}
        for site, st in _sites.items():
            sites[site] = {"compiles": st["compiles"],
                           "compile_time_s": round(st["time_s"], 4),
                           "last": (st["last"].to_dict()
                                    if st["last"] else None)}
        total_t = sum(st["time_s"] for st in _sites.values())
        total_n = sum(st["compiles"] for st in _sites.values())
        peaks = [r.peak_bytes for r in _records if r.peak_bytes]
    out = {"sites": sites,
           "totals": {"compiles": total_n,
                      "compile_time_s": round(total_t, 4),
                      "peak_bytes_max": max(peaks) if peaks else 0}}
    try:
        out["hbm"] = hbm_stats()
    except Exception:
        pass
    return out


# ---------------------------------------------------------------------------
# the instrumented jit wrapper
# ---------------------------------------------------------------------------

_FALLBACK = object()


def jit(fn, site: str, arg_names=None, **jit_kw):
    """``jax.jit`` with the compile registry on the compile path.

    Disabled (the default): returns the plain ``jax.jit`` — zero added
    work per dispatch. Enabled: returns a wrapper that, per new
    argument-aval signature, times ``lower().compile()`` into a
    :class:`CompileRecord` and then dispatches through the measured AOT
    executable itself (same donation, same executable — no second
    compile, no extra dispatch). Positional calling only, which is all
    the step-path sites use."""
    import jax

    jfn = jax.jit(fn, **jit_kw)
    if not enabled():
        return jfn
    return _InstrumentedJit(jfn, site, arg_names)


class _InstrumentedJit:
    def __init__(self, jfn, site, arg_names):
        self._jit = jfn
        self._site = site
        self._arg_names = arg_names
        self._cache: Dict[tuple, Any] = {}
        self._lock = threading.Lock()

    def lower(self, *args, **kw):
        # HLO regression gates lower() the raw jit; keep that working
        return self._jit.lower(*args, **kw)

    def _cache_size(self) -> int:
        """Executables built for this site, as ``jax.jit``'s own
        ``_cache_size``: the measured AOT ones and what the plain jit
        compiled for signatures that fell back to it."""
        with self._lock:
            aot = sum(c is not _FALLBACK for c in self._cache.values())
        return aot + self._jit._cache_size()

    def __call__(self, *args):
        sig = leaf_signature(args, self._arg_names)
        with self._lock:
            compiled = self._cache.get(sig)
            if compiled is None:
                # compiling under the lock is the point: a second
                # thread hitting the same signature must wait for the
                # one measured compile, not race a duplicate
                compiled = self._compile(args, sig)  # graft: blocking-ok
        if compiled is _FALLBACK:
            return self._jit(*args)
        try:
            return compiled(*args)
        except TypeError as e:
            # the AOT input check is stricter than jit dispatch (e.g. a
            # committed-device mismatch). The plain jit still serves the
            # call, but it COMPILES THE SITE A SECOND TIME and the
            # registry's record no longer describes what runs — so this
            # is counted and logged, never silent
            _tel.inc("compile.aot_fallback")
            _log.warning(
                "xprof: %s: the measured AOT executable rejected its "
                "arguments (%s); dispatching through jax.jit instead, "
                "which compiles this site again", self._site,
                str(e).splitlines()[0])
            with self._lock:
                self._cache[sig] = _FALLBACK
            return self._jit(*args)

    def _compile(self, args, sig):
        t0 = time.perf_counter()
        try:
            compiled = self._jit.lower(*args).compile()
        except NotImplementedError:
            self._cache[sig] = _FALLBACK
            return _FALLBACK
        rec = record_compile(self._site, compiled,
                             time.perf_counter() - t0, signature=sig)
        if _env.get("MXNET_TPU_XPROF_PREFLIGHT") and rec.peak_bytes:
            preflight_check(
                rec.peak_bytes,
                devices=compiled.runtime_executable().local_devices(),
                what=self._site)
        self._cache[sig] = compiled
        return compiled


# ---------------------------------------------------------------------------
# HLO op-category attribution
# ---------------------------------------------------------------------------

CATEGORIES = ("conv", "dot", "fusion", "collective", "transpose",
              "elementwise", "other")

_DTYPE_BYTES = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
                "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
                "token": 0, "opaque": 0}

_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\]")
_INSTR_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s=\s(.*)$")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(")

_COLLECTIVE = frozenset((
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute", "collective-broadcast", "all-reduce-start",
    "all-reduce-done", "all-gather-start", "all-gather-done",
    "collective-permute-start", "collective-permute-done",
    "partition-id", "replica-id", "send", "recv", "send-done",
    "recv-done"))
_DATA_MOVE = frozenset((
    "transpose", "copy", "reshape", "bitcast", "bitcast-convert",
    "broadcast", "slice", "dynamic-slice", "dynamic-update-slice",
    "concatenate", "gather", "scatter", "pad", "reverse", "copy-start",
    "copy-done", "iota"))
_SKIP = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "after-all",
    "domain", "opt-barrier", "add-dependency", "partition-id"))
_REDUCES = frozenset(("reduce", "reduce-window", "select-and-scatter",
                      "sort"))
# elementwise ops that actually do arithmetic (1 FLOP/elem model;
# comparisons/selects/converts are categorized elementwise at 0 FLOPs)
_ARITH = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "expm1", "log", "log1p", "logistic", "power",
    "sqrt", "rsqrt", "cbrt", "tanh", "tan", "sine", "cosine", "atan2",
    "remainder", "negate", "abs", "erf", "sign", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "clamp", "map"))


def _dtype_bytes(dt: str) -> int:
    return _DTYPE_BYTES.get(dt, 4)


def _shape_list(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every ``dtype[dims]`` token in ``text`` (operand lists carry the
    operands' shapes inline in optimized-HLO text)."""
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        if dt in _DTYPE_BYTES or dt[0] in "sufc" or dt == "pred":
            out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def _elems(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _split_instr(rhs: str):
    """(out_shapes, opcode, operand_text, attr_text) from an
    instruction's right-hand side, or None."""
    rhs = rhs.strip()
    if rhs.startswith("("):            # tuple-shaped output
        depth, i = 0, 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        out_txt, rest = rhs[:i + 1], rhs[i + 1:]
    else:
        m = _SHAPE_RE.match(rhs)
        if not m:
            return None
        rest = rhs[m.end():]
        if rest.startswith("{"):       # layout
            rest = rest[rest.index("}") + 1:]
        out_txt = rhs[:m.end()]
    rest = rest.strip()
    m = re.match(r"([\w\-]+)\(", rest)
    if not m:
        return None
    opcode = m.group(1)
    depth, j = 0, m.end() - 1
    for j in range(m.end() - 1, len(rest)):
        depth += (rest[j] == "(") - (rest[j] == ")")
        if depth == 0:
            break
    return (_shape_list(out_txt), opcode,
            rest[m.end():j], rest[j + 1:])


def _conv_flops(out_elems: int, op_shapes, attrs: str) -> int:
    ksize = 1
    m = re.search(r"size=([\dx]+)", attrs)
    if m:
        for d in m.group(1).split("x"):
            ksize *= int(d)
    cin = 1
    m = re.search(r"dim_labels=[\w?]+_([\w?]+)->", attrs)
    if m and len(op_shapes) >= 2:
        rhs_labels, rhs_shape = m.group(1), op_shapes[1][1]
        if "i" in rhs_labels and rhs_labels.index("i") < len(rhs_shape):
            cin = rhs_shape[rhs_labels.index("i")]
    m = re.search(r"feature_group_count=(\d+)", attrs)
    groups = int(m.group(1)) if m else 1
    return 2 * out_elems * ksize * cin // max(groups, 1)


def _dot_flops(out_elems: int, op_shapes, attrs: str) -> int:
    k = 1
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", attrs)
    if m and op_shapes:
        lhs_shape = op_shapes[0][1]
        for d in m.group(1).split(","):
            if d and int(d) < len(lhs_shape):
                k *= lhs_shape[int(d)]
    return 2 * out_elems * k


def hlo_op_breakdown(hlo_text: str) -> Dict[str, dict]:
    """Parse optimized HLO text into ``{category: {"flops", "bytes",
    "count"}}`` over the entry computation. FLOPs follow the standard
    analytic model (2·N·K per dot/conv MAC, 1/elem for arithmetic,
    in-elems per reduce); fused computations contribute their body's
    conv/dot FLOPs to those categories and everything else to
    ``fusion``, whose bytes are the fusion's interface traffic. The
    per-category FLOPs sum to the reported total by construction —
    cross-check against ``cost_analysis()['flops']`` lives in the
    CompileRecord beside it."""
    comps: Dict[str, list] = {}
    entry = None
    cur: Optional[list] = None
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.endswith("{") and "=" not in s.split("(")[0]:
            m = _COMP_RE.match(s)
            if m:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if s.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(s)
        if m is None:
            continue
        parsed = _split_instr(m.group(2))
        if parsed is not None:
            cur.append(parsed)
    if entry is None:          # single-computation module w/o ENTRY tag
        entry = next(iter(comps), None)
    if entry is None:
        return {}

    def classify(parsed):
        out_shapes, opcode, operands, attrs = parsed
        op_shapes = _shape_list(operands)
        out_elems = sum(_elems(d) for _dt, d in out_shapes)
        out_bytes = sum(_elems(d) * _dtype_bytes(dt)
                        for dt, d in out_shapes)
        byts = out_bytes + sum(_elems(d) * _dtype_bytes(dt)
                               for dt, d in op_shapes)
        if opcode in _SKIP:
            return None
        if opcode == "convolution":
            return "conv", _conv_flops(out_elems, op_shapes, attrs), byts
        if opcode in ("dot", "ragged-dot"):
            return "dot", _dot_flops(out_elems, op_shapes, attrs), byts
        if opcode in _COLLECTIVE:
            return "collective", 0, byts
        if opcode in _DATA_MOVE:
            return "transpose", 0, byts
        if opcode in _REDUCES:
            in_elems = sum(_elems(d) for _dt, d in op_shapes) or out_elems
            return "elementwise", in_elems, byts
        if opcode == "fusion":
            return "fusion", 0, byts       # body folded in below
        return ("elementwise", out_elems if opcode in _ARITH else 0,
                byts) if opcode in _ARITH or opcode in (
                    "compare", "select", "convert", "and", "or", "xor",
                    "not", "is-finite", "shift-left",
                    "shift-right-logical", "shift-right-arithmetic",
                    "exponential-minus-one", "rng", "rng-bit-generator",
                    "reduce-precision", "real", "imag", "complex",
        ) else ("other", 0, byts)

    memo: Dict[str, Dict[str, int]] = {}

    def body_flops(name, stack=()):
        """Per-category FLOPs of a computation body (bytes inside a
        fusion are not real memory traffic and are not counted)."""
        if name in memo:
            return memo[name]
        if name in stack or name not in comps:
            return {}
        totals: Dict[str, int] = {}
        for parsed in comps[name]:
            cl = classify(parsed)
            if cl is None:
                continue
            cat, fl, _by = cl
            if cat == "fusion":
                m = re.search(r"calls=%?([\w.\-]+)", parsed[3])
                if m:
                    for c, f in body_flops(m.group(1),
                                           stack + (name,)).items():
                        c = c if c in ("conv", "dot") else "fusion"
                        totals[c] = totals.get(c, 0) + f
                continue
            totals[cat] = totals.get(cat, 0) + fl
        memo[name] = totals
        return totals

    agg = {c: {"flops": 0, "bytes": 0, "count": 0} for c in CATEGORIES}
    coll_ops: Dict[str, Dict[str, int]] = {}
    for parsed in comps[entry]:
        cl = classify(parsed)
        if cl is None:
            continue
        cat, fl, by = cl
        agg[cat]["bytes"] += by
        agg[cat]["count"] += 1
        if cat == "collective":
            # per-opcode sub-buckets: an fsdp step's all-gather
            # (param gather before forward) and reduce-scatter (grad
            # shard-reduce) are distinguishable from the dp all-reduce
            op = parsed[1]
            sub = coll_ops.setdefault(op, {"bytes": 0, "count": 0})
            sub["bytes"] += by
            sub["count"] += 1
        if cat == "fusion":
            m = re.search(r"calls=%?([\w.\-]+)", parsed[3])
            sub = body_flops(m.group(1), (entry,)) if m else {}
            for c, f in sub.items():
                c = c if c in ("conv", "dot") else "fusion"
                agg[c]["flops"] += f
        else:
            agg[cat]["flops"] += fl
    if coll_ops:
        agg["collective"]["by_op"] = coll_ops
    return {c: v for c, v in agg.items()
            if v.get("count") or v.get("flops")}


# ---------------------------------------------------------------------------
# analytic MFU / roofline classification
# ---------------------------------------------------------------------------

# The ONE peak table: published per-chip (bf16 TFLOP/s, HBM GB/s), keyed
# by a fragment of jax's ``device_kind``. Source: Google Cloud TPU
# documentation, the per-generation system-architecture pages ("TPU v5e":
# 197 TFLOP/s bf16, 819 GB/s HBM; likewise v2/v3/v4/v5p/v6e). A v5e
# reports ``device_kind == "TPU v5 lite"``.
CHIP_PEAKS = {"v5 lite": (197, 819), "v5litepod": (197, 819),
              "v5e": (197, 819), "v5p": (459, 2765), "v4": (275, 1228),
              "v6 lite": (918, 1640), "v6e": (918, 1640),
              "v3": (123, 900), "v2": (45, 700)}


def chip_peaks(device_kind: Optional[str]):
    """``(peak bf16 TFLOP/s, HBM GB/s)`` for a device kind. ``"cpu"`` is
    the explicit "no peak" and returns None; any other kind missing from
    :data:`CHIP_PEAKS` raises — a utilisation computed against a guessed
    or absent peak is worse than no number."""
    kind = (device_kind or "").lower()
    if kind == "cpu":
        return None
    for frag in sorted(CHIP_PEAKS, key=len, reverse=True):
        if frag in kind:
            return CHIP_PEAKS[frag]
    raise MXNetError(
        "no published peak for device_kind %r: add it to "
        "xprof.CHIP_PEAKS with its source" % (device_kind,))


def chip_peak_tflops(device_kind: Optional[str]):
    peaks = chip_peaks(device_kind)
    return peaks[0] if peaks else None


def chip_hbm_gbps(device_kind: Optional[str]):
    peaks = chip_peaks(device_kind)
    return peaks[1] if peaks else None


def analyze(flops, bytes_accessed, step_time_s=None,
            device_kind: Optional[str] = None) -> dict:
    """Roofline analytics for one executable: arithmetic intensity,
    the chip's ridge point, compute- vs bandwidth-bound, and (given a
    measured step time) achieved TFLOP/s + analytic MFU. On the CPU
    there is no peak: ``bound`` is ``"unknown"`` and NO
    ``analytic_mfu_pct`` field is emitted (never a 0.0 that reads as a
    measurement); the FLOP counts are still attached. ``device_kind``
    defaults to the first device's."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    peak, bw = chip_peaks(device_kind) or (None, None)
    out = {"flops": flops, "bytes_accessed": bytes_accessed,
           "device_kind": device_kind,
           "peak_tflops": peak, "hbm_gbps": bw}
    ai = (float(flops) / float(bytes_accessed)
          if flops and bytes_accessed else None)
    ridge = (peak * 1e12) / (bw * 1e9) if peak else None
    out["arithmetic_intensity"] = round(ai, 2) if ai else None
    out["ridge_intensity"] = round(ridge, 2) if ridge else None
    out["bound"] = (("compute" if ai >= ridge else "bandwidth")
                    if ai is not None and ridge is not None else "unknown")
    if step_time_s and flops:
        achieved = float(flops) / float(step_time_s)
        out["achieved_tflops"] = round(achieved / 1e12, 3)
        if peak:
            out["analytic_mfu_pct"] = round(
                100.0 * achieved / (peak * 1e12), 2)
    return out


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------

def hbm_stats(device=None) -> dict:
    """Live-buffer accounting FOR ONE DEVICE: ``device.memory_stats()``
    where the backend provides it (TPU), else ``jax.live_arrays()``
    (CPU — no allocator limit, so ``limit_bytes`` is None). The
    live_arrays walk is per-device exact: a sharded array contributes
    only the bytes of its shards resident on ``device`` (an
    fsdp-sharded pack bills 1/fsdp per chip), never its GLOBAL
    ``nbytes`` — billing the whole pack to device 0 is precisely the
    accounting bug a sharded mesh exposes."""
    import jax

    try:
        dev = device if device is not None else jax.devices()[0]
    except Exception:
        return {"live_bytes": 0, "limit_bytes": None,
                "peak_bytes": None, "source": "none"}
    ms = None
    try:
        ms = dev.memory_stats()
    except Exception:
        ms = None
    if ms and ms.get("bytes_in_use") is not None:
        return {"live_bytes": int(ms.get("bytes_in_use", 0)),
                "limit_bytes": (int(ms["bytes_limit"])
                                if ms.get("bytes_limit") else None),
                "peak_bytes": (int(ms["peak_bytes_in_use"])
                               if ms.get("peak_bytes_in_use") else None),
                "source": "memory_stats"}
    live = 0
    for arr in jax.live_arrays():
        try:
            shards = getattr(arr, "addressable_shards", None)
            if shards:
                for s in shards:
                    if s.device == dev:
                        live += int(s.data.nbytes)
            else:
                live += int(arr.nbytes)
        except Exception:
            pass
    return {"live_bytes": live, "limit_bytes": None,
            "peak_bytes": None, "source": "live_arrays"}


class HbmWatermark:
    """Per-step live-buffer watermark. ``sample()`` after each step;
    ``peak`` is monotone over the run and the ``hbm.*`` gauges
    (including ``hbm.headroom_bytes``, exported by the MetricsServer)
    track the latest sample. ``limit_bytes`` overrides the device
    limit where the backend reports none (CPU tests)."""

    def __init__(self, device=None, limit_bytes: Optional[int] = None):
        self.device = device
        self.limit = limit_bytes
        self.peak = 0
        self.last = 0

    def sample(self) -> int:
        s = hbm_stats(self.device)
        self.last = s["live_bytes"]
        if self.limit is None:
            self.limit = s["limit_bytes"]
        self.peak = max(self.peak, self.last, s["peak_bytes"] or 0)
        if _tel.enabled():
            _tel.set_gauge("hbm.live_bytes", self.last)
            _tel.set_gauge("hbm.peak_bytes", self.peak)
            if self.limit:
                _tel.set_gauge("hbm.headroom_bytes",
                               self.limit - self.last)
        return self.last

    @property
    def headroom_bytes(self) -> Optional[int]:
        return self.limit - self.last if self.limit else None


def device_memory_limit(device=None) -> Optional[int]:
    try:
        import jax
        dev = device if device is not None else jax.devices()[0]
        ms = dev.memory_stats()
        if ms and ms.get("bytes_limit"):
            return int(ms["bytes_limit"])
    except Exception:
        pass
    return None


def _fmt_bytes(n) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return ("%d%s" if unit == "B" else "%.1f%s") % (n, unit)
        n /= 1024.0


def preflight_check(peak_bytes, limit_bytes: Optional[int] = None,
                    device=None, devices=None, what: str = "computation"):
    """Refuse a config before it runs: raise :class:`MXNetError` when
    the executable's ``memory_analysis`` peak exceeds the device HBM
    limit. Returns the headroom in bytes, or None when no limit is
    known (CPU) — the check is advisory there by design.

    ``memory_analysis`` reports PER-PARTITION bytes for an SPMD
    executable (each device holds only its shard of arguments, temps
    and outputs), so the comparison is per-device by construction:
    pass ``devices`` (the executable's local devices) and the peak is
    checked against the SMALLEST per-device limit among them — NOT
    against device 0's limit with the whole pack billed to it."""
    if limit_bytes is None and devices:
        limits = [device_memory_limit(d) for d in devices]
        limits = [l for l in limits if l]
        limit_bytes = min(limits) if limits else None
    if limit_bytes is None:
        limit_bytes = device_memory_limit(device)
    if not limit_bytes or not peak_bytes:
        return None
    headroom = int(limit_bytes) - int(peak_bytes)
    if headroom < 0:
        raise MXNetError(
            "pre-flight OOM: %s needs %s at peak but the device limit "
            "is %s (short %s) — shrink the batch or shard the model"
            % (what, _fmt_bytes(int(peak_bytes)),
               _fmt_bytes(int(limit_bytes)), _fmt_bytes(-headroom)))
    return headroom
