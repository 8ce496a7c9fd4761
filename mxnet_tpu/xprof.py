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
  argument/output/alias/temp/code bytes, per site, as the gauges
  ``compile.<site>.*`` (``held_bytes`` is what the program holds at
  once by XLA's own account). A recompile carries a *retrace-cause
  diff* naming exactly which avals changed vs the previous signature —
  "(64,3,224,224)f32 -> (32,3,224,224)f32 on batch.data" instead of
  "something retraced". It follows telemetry's one switch: on when
  ``telemetry.enabled()`` at the moment a site is built.
* **A build names itself** — the record also says WHAT was built
  (:func:`program_identity`: a hash of the lowered module's text and one
  of each Pallas kernel's payload, apart), whether the persistent cache
  answered and the build's seconds by JAX's own phases;
  :func:`diff_builds` tells two records, of two processes if need be,
  apart by the part that differs. Every site lowers to the same bytes
  however it is reached: this repo's kernels carry no Python frame
  (``ops/pallas_kernels.py``: ``_strip_locations``).
* **Op-category attribution** — :func:`hlo_op_breakdown` parses the
  compiled executable's optimized HLO into a conv / dot / fusion /
  collective / transpose / elementwise FLOP+bytes table whose category
  sums ARE the reported totals (exact by construction), so the
  measured-vs-analytic MFU gap is attributable to a specific category.
  :func:`analyze` adds analytic MFU, arithmetic intensity and a
  compute- vs bandwidth-bound classification from the chip's peak
  FLOPs and HBM bandwidth.
* **Census by phase** — for a site that asks (``census=True``: the
  fused step) the same parse groups the program's top-level
  instructions by the SET of phases (``fwd`` / ``recompute`` / ``bwd``
  / ``update`` / ``metric``) their members' ``op_name`` carry:
  :func:`hlo_phase_census`, published as ``compile.<site>.census.*``.
  A fusion that holds a weight-gradient product AND the optimizer's
  update reads ``bwd+update``: what a device trace charges to one scope.
* **HBM accounting** — :func:`hbm_stats` reads one device's
  ``memory_stats()`` (``jax.live_arrays()`` on the CPU); a module
  publishes an allocator's answer at a fence as ``device.hbm_*``
  (``Module.publish_aux_counters``), and :func:`preflight_check`
  refuses a config whose ``memory_analysis`` footprint cannot fit
  before a single step runs.

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

import contextlib
import hashlib
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
    "records", "summary", "last_retrace_cause", "program_identity",
    "diff_builds", "hlo_op_breakdown",
    "hlo_phase_census", "analyze", "chip_peaks", "chip_peak_tflops",
    "chip_hbm_gbps", "CHIP_PEAKS", "hbm_stats",
    "preflight_check", "device_memory_limit",
    "CompileRecord", "CATEGORIES", "PHASES",
]

# ---------------------------------------------------------------------------
# enablement
# ---------------------------------------------------------------------------

_override: Optional[bool] = None


def enabled() -> bool:
    """Telemetry's switch is this one's: a site built while
    ``telemetry.enabled()`` is instrumented. ``enable()`` / ``disable()``
    override it either way (tests)."""
    if _override is not None:
        return _override
    return _tel.enabled()


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
    """One measured ``lower()``/``compile()`` of a step-path site.
    ``module_sha`` / ``kernels`` say what was built
    (:func:`program_identity`); ``cache`` whether JAX's persistent cache
    answered (``"read"``), took what XLA built (``"built"``) or had no
    part (``"off"``: no directory, or a program under JAX's thresholds of
    a second and a size); ``trace_s``, ``lower_s``,
    ``cache_read_s`` and ``backend_compile_s`` are ``compile_time_s`` by
    JAX's own phases, the last LESS the cache read it encloses. None on a
    record made from an executable alone (:func:`record_compile` without
    ``identity`` / ``build``)."""

    __slots__ = ("site", "seq", "compile_time_s", "signature", "flops",
                 "bytes_accessed", "argument_bytes", "output_bytes",
                 "alias_bytes", "temp_bytes", "generated_code_bytes",
                 "held_bytes", "op_breakdown", "census", "matrix_flops",
                 "census_loops_once", "retrace_cause", "num_devices", "ts",
                 "module_sha", "kernels", "cache", "trace_s", "lower_s",
                 "cache_read_s", "backend_compile_s")

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))

    def to_dict(self) -> dict:
        d = {k: getattr(self, k) for k in self.__slots__}
        d["signature"] = _signature_rows(self.signature)
        if self.kernels is not None:
            d["kernels"] = [list(k) for k in self.kernels]
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


def _signature_rows(signature) -> list:
    """A signature as ``to_dict()`` writes it: ``[name, shape, dtype]``
    with the placement behind where there is one."""
    return [[n, list(a[0]), a[1]] + ([a[3]] if len(a) > 3 and a[3] else [])
            for n, a in (signature or ())]


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


# -- what was built ---------------------------------------------------------

# a Pallas kernel in a lowered module's text: the custom call, its payload
# (``backend_config``: the serialized Mosaic module, in which MLIR writes a
# quote as ``\22``, so the first bare quote ends it) and, behind it on the
# same line, the name the kernel was given
_PAYLOAD_RE = re.compile(
    rb'@tpu_custom_call\([^\n]*?backend_config = "([^"]*)"')
_KERNEL_NAME_RE = re.compile(rb'kernel_name = "([^"]*)"')


def program_identity(text: str):
    """``(module_sha, kernels)`` of a lowered module's text
    (``lowered.as_text()``): the sha256 of the text with every
    ``tpu_custom_call``'s payload cut out, and ``[(kernel's name, sha256
    of its payload)]`` in program order. JAX strips locations from the
    module it hashes for the persistent cache; it cannot look inside a
    payload, so two programs that miss each other's entry differ in one
    of these parts, and the parts say in which."""
    # one copy of a text that runs to hundreds of MB (a language step's
    # tables are constants in it), hashed in place
    data = text.encode()
    view = memoryview(data)
    module, kernels, pos = hashlib.sha256(), [], 0
    for m in _PAYLOAD_RE.finditer(data):
        module.update(view[pos:m.start(1)])
        pos = m.end(1)
        eol = data.find(b"\n", pos)
        name = _KERNEL_NAME_RE.search(data, pos,
                                      eol if eol >= 0 else len(data))
        kernels.append((name.group(1).decode() if name
                        else "tpu_custom_call",
                        hashlib.sha256(view[m.start(1):pos]).hexdigest()))
    module.update(view[pos:])
    return module.hexdigest(), kernels


def diff_builds(a, b) -> Optional[str]:
    """Which part of two builds differs, in :func:`diff_signatures`'
    manner, or None: ``arguments: ...`` (the signature diff), ``module
    text outside the kernels``, ``kernel <name> (#i): payload`` by the
    kernels' places in the program, ``kernels: 10 against 9``. Each side
    a :class:`CompileRecord` or its ``to_dict()`` (a site's ``last`` in a
    saved :func:`summary`), so that two PROCESSES' builds of "the same"
    step compare: a miss of the persistent cache on a step nobody changed
    is one call to read."""
    a, b = (x.to_dict() if isinstance(x, CompileRecord) else x
            for x in (a, b))

    def signature(build):
        return tuple((r[0], (tuple(r[1]), r[2], False,
                             r[3] if len(r) > 3 else None))
                     for r in build.get("signature") or ())

    found = []
    arguments = diff_signatures(signature(a), signature(b))
    if arguments:
        found.append("arguments: " + arguments)
    if a.get("module_sha") != b.get("module_sha"):
        found.append("module text outside the kernels")
    ka, kb = a.get("kernels") or [], b.get("kernels") or []
    if len(ka) != len(kb):
        found.append("kernels: %d against %d" % (len(ka), len(kb)))
    else:
        places: Dict[str, list] = {}
        for i, ((na, sha_a), (nb, sha_b)) in enumerate(zip(ka, kb)):
            if na != nb:
                found.append("kernel %s against %s (#%d)" % (na, nb, i))
            elif sha_a != sha_b:
                places.setdefault(na, []).append("#%d" % i)
        found += ["kernel %s (%s): payload" % (name, ", ".join(at))
                  for name, at in places.items()]
    return "; ".join(found) or None


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
    # what the program holds at once, by XLA's own account. Aliased
    # (donated) buffers are counted once: they are argument bytes XLA
    # reuses for outputs, not extra memory
    out["held_bytes"] = max(0, out["argument_bytes"] + out["output_bytes"]
                            + out["temp_bytes"]
                            + out["generated_code_bytes"]
                            - out["alias_bytes"])
    return out


def _read_program(site: str, compiled, census: bool):
    """(op breakdown, census, matrix FLOPs by phase, loops counted once)
    from the executable's optimised HLO text. A site that asks for the
    census (the fused step: it traces under the phases' scopes) gets all
    four, read under the span ``step.census`` so that what the parse
    costs shows in its build; any other the op breakdown alone. Nones
    with ``MXNET_TPU_XPROF_OPS`` off, and for a text the parser cannot
    read: one warning, never a failed build."""
    if not _env.get("MXNET_TPU_XPROF_OPS"):
        return (None,) * 4
    try:
        if not census:
            return (hlo_op_breakdown(compiled.as_text()),) + (None,) * 3
        with _tel.span("step.census"):
            return _analyze_hlo(compiled.as_text())
    except Exception as e:          # the text's form is XLA's to change
        _log.warning("xprof: %s: the optimised HLO was not read (%s: %s); "
                     "no op breakdown and no census for this program",
                     site, type(e).__name__, e)
        return (None,) * 4


def record_compile(site: str, compiled, compile_time_s: float,
                   signature: Optional[tuple] = None,
                   census: bool = False, identity=None,
                   build: Optional[dict] = None) -> CompileRecord:
    """Record one measured compile into the registry and, with telemetry
    on, into the gauges ``compile.<site>.*`` (one set a site, the latest
    program's); computes the retrace-cause diff against the site's
    previous signature. ``census``: group the program's instructions by
    phase too (:func:`hlo_phase_census`). ``identity``: what
    :func:`program_identity` made of the lowered text; ``build``: what
    ``telemetry.jax_build`` collected while the program was lowered and
    compiled."""
    global _last_cause, _seq
    named = {}
    if identity is not None:
        named["module_sha"], named["kernels"] = identity
    if build is not None:
        read = build["jax.cache_read"]
        named.update(
            cache=build["cache"], trace_s=round(build["jax.trace"], 6),
            lower_s=round(build["jax.lower"], 6),
            cache_read_s=round(read, 6),
            # JAX times the backend's compile around the cache read
            backend_compile_s=round(
                max(0.0, build["jax.backend_compile"] - read), 6))
    cost = _cost_dict(compiled)
    mem = _memory_dict(compiled) or {}
    breakdown, by_set, matrix_flops, loops_once = _read_program(
        site, compiled, census)
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
            op_breakdown=breakdown, census=by_set,
            matrix_flops=matrix_flops, census_loops_once=loops_once,
            retrace_cause=cause,
            num_devices=_device_count(compiled),
            ts=round(time.time(), 6), **mem, **named)
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
        _publish(rec)
    return rec


_SITE_GAUGES = ("argument_bytes", "output_bytes", "alias_bytes",
                "temp_bytes", "generated_code_bytes", "held_bytes",
                "flops", "bytes_accessed")


def _publish(rec: CompileRecord):
    """The record as gauges: ``compile.<site>.<field>`` for what XLA
    reported, ``.build_s``, ``compile.<site>.census.<set>.ops`` /
    ``.flops`` / ``.bytes`` for the sets of phases that occur and
    ``.matrix_flops.<phase>`` for the products by their own phase, where
    the site asked for the census; of a build that was watched
    ``.cache_read`` (1: the persistent cache answered; 0: XLA built the
    program, or the cache is off). The build's other parts are the
    record's (:func:`summary`, ``trace_report --view compile``)."""
    base = "compile.%s." % rec.site
    for field in _SITE_GAUGES:
        v = getattr(rec, field)
        if v is not None:
            _tel.set_gauge(base + field, v)
    _tel.set_gauge(base + "build_s", rec.compile_time_s)
    if rec.cache is not None:
        _tel.set_gauge(base + "cache_read", int(rec.cache == "read"))
    for name, row in (rec.census or {}).items():
        for field, v in row.items():
            _tel.set_gauge("%scensus.%s.%s" % (base, name, field), v)
    for phase, v in (rec.matrix_flops or {}).items():
        _tel.set_gauge("%smatrix_flops.%s" % (base, phase), v)


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
        held = [r.held_bytes for r in _records if r.held_bytes]
    out = {"sites": sites,
           "totals": {"compiles": total_n,
                      "compile_time_s": round(total_t, 4),
                      "held_bytes_max": max(held) if held else 0}}
    try:
        out["hbm"] = hbm_stats()
    except Exception:
        pass
    return out


# ---------------------------------------------------------------------------
# the instrumented jit wrapper
# ---------------------------------------------------------------------------

_FALLBACK = object()


def jit(fn, site: str, arg_names=None, census=False, **jit_kw):
    """``jax.jit`` with the compile registry on the compile path.

    Disabled (the default): returns the plain ``jax.jit`` — zero added
    work per dispatch. Enabled: returns a wrapper that, per new
    argument-aval signature, times ``lower().compile()`` into a
    :class:`CompileRecord` and then dispatches through the measured AOT
    executable itself (same donation, same executable — no second
    compile, no extra dispatch). Positional calling only, which is all
    the step-path sites use. ``census``: the site traces under the
    phases' scopes (:data:`PHASES`) and wants its program's instructions
    grouped by them, under the span ``step.census`` (and its identity
    hashed under ``step.identity``)."""
    import jax

    jfn = jax.jit(fn, **jit_kw)
    if not enabled():
        return jfn
    return _InstrumentedJit(jfn, site, arg_names, census)


class _InstrumentedJit:
    def __init__(self, jfn, site, arg_names, census=False):
        self._jit = jfn
        self._site = site
        self._arg_names = arg_names
        self._census = census
        self._cache: Dict[tuple, Any] = {}
        # (signature, executable) of the last call: the steady state's
        # whole lookup (the executable's own input check tells a new
        # signature apart)
        self._last = None
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
        last = self._last
        if last is not None:
            # no walk over the leaves in steady state: the executable
            # checks its arguments' types (``TypeError``) and placement
            # (``ValueError``) itself, before anything runs or is
            # donated; whether that was another signature, the walk says
            try:
                return last[1](*args)
            except (TypeError, ValueError) as e:
                sig = leaf_signature(args, self._arg_names)
                if sig == last[0]:
                    # no new signature: an input check stricter than the
                    # signature, or the call's own error, raised once
                    if isinstance(e, TypeError):
                        return self._refused(sig, args, e)
                    raise
        else:
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
            res = compiled(*args)
        except TypeError as e:
            return self._refused(sig, args, e)
        with self._lock:
            self._last = (sig, compiled)
        return res

    def _refused(self, sig, args, e):
        """The AOT input check is stricter than jit dispatch (e.g. a
        committed-device mismatch). The plain jit still serves the call,
        and this signature's later ones, but it COMPILES THE SITE A
        SECOND TIME and the registry's record no longer describes what
        runs — so this is counted and logged, never silent."""
        _tel.inc("compile.aot_fallback")
        _log.warning(
            "xprof: %s: the measured AOT executable rejected its "
            "arguments (%s); dispatching through jax.jit instead, "
            "which compiles this site again", self._site,
            str(e).splitlines()[0])
        with self._lock:
            self._cache[sig] = _FALLBACK
            self._last = None
        return self._jit(*args)

    def _compile(self, args, sig):
        t0 = time.perf_counter()
        try:
            with _tel.jax_build() as build:
                lowered = self._jit.lower(*args)
                compiled = lowered.compile()
        except NotImplementedError:
            self._cache[sig] = _FALLBACK
            return _FALLBACK
        build_s = time.perf_counter() - t0
        # as the census: what the hashing costs shows in the build of the
        # site that asked (the fused step's ``step.build``)
        with _tel.span("step.identity") if self._census \
                else contextlib.nullcontext():
            identity = program_identity(lowered.as_text())
        rec = record_compile(self._site, compiled, build_s, signature=sig,
                             census=self._census, identity=identity,
                             build=build)
        if _env.get("MXNET_TPU_XPROF_PREFLIGHT") and rec.held_bytes:
            preflight_check(
                rec.held_bytes,
                devices=compiled.runtime_executable().local_devices(),
                what=self._site)
        self._cache[sig] = compiled
        return compiled


# ---------------------------------------------------------------------------
# HLO op-category attribution and the census by phase
# ---------------------------------------------------------------------------

CATEGORIES = ("conv", "dot", "fusion", "collective", "transpose",
              "elementwise", "other")
#: the phases the fused step traces under (``jax.named_scope``), in the
#: order a set's name joins them. ``recompute`` is what ``jax.checkpoint``
#: runs again inside the backward pass
PHASES = ("fwd", "recompute", "bwd", "update", "metric")

_DTYPE_BYTES = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
                "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
                "token": 0, "opaque": 0}

# ``dtype[dims]`` with its layout, where the text gives one
_SHAPE_RE = re.compile(r"([a-z]\w*)\[([\d,]*)\](\{[^{}]*\})?")
# a layout's memory space: S(1) and up are on the chip (VMEM, flags)
_OFF_HBM_RE = re.compile(r"S\([1-9]\d*\)")
_INSTR_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s=\s(.*)$")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_PHASE_RE = re.compile(r"/(fwd|bwd|update|metric)(?:/|$)")
_TRIPS_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_COMMENT_RE = re.compile(r"/\*.*?\*/")

_COLLECTIVE = frozenset((
    "all-reduce", "all-gather", "all-to-all", "reduce-scatter",
    "collective-permute", "collective-broadcast", "partition-id",
    "replica-id", "send", "recv"))
_DATA_MOVE = frozenset((
    "transpose", "copy", "reshape", "bitcast-convert",
    "broadcast", "slice", "dynamic-slice", "dynamic-update-slice",
    "concatenate", "gather", "scatter", "pad", "reverse", "iota"))
# what moves nothing and computes nothing
_SKIP = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "after-all",
    "domain", "opt-barrier", "add-dependency", "partition-id", "bitcast"))
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
_LOGIC = frozenset((
    "compare", "select", "convert", "and", "or", "xor", "not",
    "is-finite", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "exponential-minus-one", "rng",
    "rng-bit-generator", "reduce-precision", "real", "imag", "complex"))


class _Instr:
    """One parsed instruction: its name, result shapes, opcode, operand
    text and everything after the operands (attributes, metadata)."""

    __slots__ = ("name", "shapes", "opcode", "operands", "attrs")

    def __init__(self, name, shapes, opcode, operands, attrs):
        self.name, self.shapes, self.opcode = name, shapes, opcode
        self.operands, self.attrs = operands, attrs


def _dtype_bytes(dt: str) -> int:
    return _DTYPE_BYTES.get(dt, 4)


def _shape_list(text: str) -> List[Tuple[str, Tuple[int, ...], bool]]:
    """``(dtype, dims, in HBM)`` of every ``dtype[dims]`` token in
    ``text``; a shape whose layout names a memory space on the chip
    (``S(1)``: the compiler keeps it in VMEM) is no HBM traffic."""
    out = []
    for dt, dims, layout in _SHAPE_RE.findall(text):
        if dt in _DTYPE_BYTES or dt[0] in "sufc" or dt == "pred":
            out.append((dt, tuple(int(d) for d in dims.split(",") if d),
                        not _OFF_HBM_RE.search(layout)))
    return out


def _elems(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _nbytes(shapes, hbm_only=True) -> int:
    return sum(_elems(d) * _dtype_bytes(dt) for dt, d, hbm in shapes
               if hbm or not hbm_only)


def _split_instr(name: str, rhs: str) -> Optional[_Instr]:
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
        out_txt, rest = rhs[:m.end()], rhs[m.end():]
    rest = rest.strip()
    m = re.match(r"([\w\-]+)\(", rest)
    if not m:
        return None
    depth, j = 0, m.end() - 1
    for j in range(m.end() - 1, len(rest)):
        depth += (rest[j] == "(") - (rest[j] == ")")
        if depth == 0:
            break
    return _Instr(name, _shape_list(out_txt), m.group(1),
                  rest[m.end():j], rest[j + 1:])


def _refs(operands: str) -> List[str]:
    """The instruction names an operand list refers to."""
    return [tok.split()[-1].lstrip("%")
            for tok in _COMMENT_RE.sub("", operands).split(",")
            if tok.strip()]


def _attr(attrs: str, key: str) -> Optional[str]:
    """The computation ``key=%name`` names (``calls``, ``body``, ...)."""
    m = re.search(r"\b%s=%%?([\w.\-]+)" % key, attrs)
    return m.group(1) if m else None


def _phase(attrs: str) -> Optional[str]:
    """The phase an instruction's ``op_name`` path carries, if any."""
    m = _OP_NAME_RE.search(attrs)
    ph = _PHASE_RE.search(m.group(1)) if m else None
    if ph is None:
        return None
    if ph.group(1) == "bwd" and "rematted_computation" in m.group(1):
        return "recompute"
    return ph.group(1)


def _window(attrs: str, key: str, rank: int, default: int):
    """One field of ``window={...}`` as ``rank`` ints (``stride=2x2``;
    of ``pad=1_2x1_2`` the low sides)."""
    m = re.search(r"\b%s=([\d_x\-]+)" % key, attrs)
    return [int(d.split("_")[0]) for d in m.group(1).split("x")] if m \
        else [default] * rank


def _conv_flops(out_shapes, op_shapes, attrs: str) -> int:
    """2 a multiply-add that meets a real input element. The window's
    taps that fall on padding or between the elements of a dilated
    input are not work: the TPU compiler writes a 1x1 convolution's
    gradient as a correlation over a window as large as the image,
    padded by as much, and a strided layer's as one over a dilated
    input, and 2 x outputs x window x channels would count those forty-
    and fourfold."""
    m = re.search(r"dim_labels=([\w?]+)_([\w?]+)->([\w?]+)", attrs)
    if not m or len(op_shapes) < 2 or not out_shapes:
        return 0
    lhs_l, rhs_l, out_l = m.groups()
    lhs, rhs, out = op_shapes[0][1], op_shapes[1][1], out_shapes[0][1]
    if (len(lhs_l), len(rhs_l), len(out_l)) != (len(lhs), len(rhs),
                                                len(out)):
        return 0
    spatial = [c for c in out_l if c.isdigit()]
    rank = len(spatial)
    stride = _window(attrs, "stride", rank, 1)
    lhs_dil = _window(attrs, "lhs_dilate", rank, 1)
    rhs_dil = _window(attrs, "rhs_dilate", rank, 1)
    pad_lo = _window(attrs, "pad", rank, 0)
    macs = _elems(out) * (rhs[rhs_l.index("i")] if "i" in rhs_l else 1)
    for n, c in enumerate(sorted(spatial)):
        # window fields are in the order of the spatial labels 0, 1, ...
        size_in = lhs[lhs_l.index(c)]
        size_k = rhs[rhs_l.index(c)]
        size_out = out[out_l.index(c)]
        last = (size_in - 1) * lhs_dil[n]
        taps = 0
        for o in range(size_out):
            first = o * stride[n] - pad_lo[n]
            for k in range(size_k):
                pos = first + k * rhs_dil[n]
                taps += 0 <= pos <= last and pos % lhs_dil[n] == 0
        macs = macs * taps // max(size_out, 1)
    return 2 * macs


def _dot_flops(out_elems: int, op_shapes, attrs: str) -> int:
    k = 1
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", attrs)
    if m and op_shapes:
        lhs_shape = op_shapes[0][1]
        for d in m.group(1).split(","):
            if d and int(d) < len(lhs_shape):
                k *= lhs_shape[int(d)]
    return 2 * out_elems * k


def _parse_hlo(hlo_text: str):
    """``(computations, entry, shapes by instruction name)``."""
    comps: Dict[str, List[_Instr]] = {}
    defs: Dict[str, list] = {}
    entry = None
    cur: Optional[list] = None
    last: Optional[_Instr] = None
    for line in hlo_text.splitlines():
        s = line.strip()
        if cur is None:
            if s.endswith("{") and "=" not in s.split("(")[0]:
                m = _COMP_RE.match(s)
                if m:
                    cur = comps.setdefault(m.group(2), [])
                    last = None
                    if m.group(1):
                        entry = m.group(2)
            continue
        if s == "}":
            cur = None
            continue
        m = _INSTR_RE.match(s)
        if m is None:
            # a quoted attribute that spans lines (a Pallas kernel's
            # metadata): what follows it, op_name too, is this line's
            if last is not None:
                last.attrs += " " + s
            continue
        last = _split_instr(m.group(1), m.group(2))
        if last is not None:
            cur.append(last)
            defs[last.name] = last.shapes
    if entry is None:          # single-computation module w/o ENTRY tag
        entry = next(iter(comps), None)
    return comps, entry, defs


def _analyze_hlo(hlo_text: str):
    """One walk of the optimised HLO text, two tables.

    Walked are the *top-level* instructions: the entry computation's
    and, in its place, a ``while``'s body once a trip (the trip count
    from ``known_trip_count`` or a condition ``i < constant``; where
    neither says it the body counts once, and the third result counts
    such loops). Each gives its FLOPs (2 N K a dot or convolution MAC,
    those inside a fusion's body included; 1 an element for arithmetic,
    the inputs' elements for a reduce) and its bytes: operands read +
    results written, its least HBM traffic (what the compiler keeps on
    the chip, memory space ``S(1)``, is none; an in-place update counts
    its whole operand; an asynchronous pair counts once, at its
    ``-done``; a custom call, a Pallas kernel, counts bytes and no
    FLOPs).

    Returns ``(by category, by set of phases, matrix FLOPs by phase,
    loops counted once)``: ``{category: {"flops", "bytes", "count"}}``
    as :func:`hlo_op_breakdown` documents, ``{set: {"ops", "flops",
    "bytes"}}`` as :func:`hlo_phase_census` does, and ``{phase:
    FLOPs}``: every convolution and dot under the phase of its OWN
    ``op_name`` (a fusion's bytes cannot be told apart by phase; its
    products can). The convolution and dot FLOPs of the three tables are
    the same sum."""
    comps, entry, defs = _parse_hlo(hlo_text)
    if entry is None:
        return {}, {}, {}, 0

    def operand_shapes(ins):
        inline = _shape_list(ins.operands)
        if inline:
            return inline
        return [sh for ref in _refs(ins.operands)
                for sh in defs.get(ref, ())]

    def classify(ins):
        opcode = ins.opcode
        if opcode in _SKIP or opcode.endswith("-start") \
                or 'custom_call_target="AllocateBuffer"' in ins.attrs:
            return None
        out_elems = sum(_elems(d) for _dt, d, _hbm in ins.shapes)
        if opcode.endswith("-done"):
            # the pair's one transfer: one of its two sides is HBM
            opcode = opcode[:-len("-done")]
            op_shapes, byts = [], _nbytes(ins.shapes, hbm_only=False)
        else:
            op_shapes = operand_shapes(ins)
            byts = _nbytes(ins.shapes) + _nbytes(op_shapes)
        if opcode == "convolution":
            return "conv", _conv_flops(ins.shapes, op_shapes,
                                       ins.attrs), byts
        if opcode in ("dot", "ragged-dot"):
            return "dot", _dot_flops(out_elems, op_shapes, ins.attrs), byts
        if opcode in _COLLECTIVE:
            return "collective", 0, byts
        if opcode in _DATA_MOVE:
            return "transpose", 0, byts
        if opcode in _REDUCES:
            in_elems = sum(_elems(d) for _dt, d, _hbm in op_shapes) \
                or out_elems
            return "elementwise", in_elems, byts
        if opcode == "fusion":
            return "fusion", 0, byts       # body folded in below
        if opcode in _ARITH:
            return "elementwise", out_elems, byts
        return ("elementwise" if opcode in _LOGIC else "other"), 0, byts

    flops_memo: Dict[str, dict] = {}

    def body_flops(name, stack=()):
        """FLOPs of a computation body by category, a convolution's or a
        dot's under ``(category, its own phase)`` (bytes inside a fusion
        are not real memory traffic and are not counted)."""
        if name in flops_memo:
            return flops_memo[name]
        if name in stack or name not in comps:
            return {}
        totals: dict = {}
        for ins in comps[name]:
            cl = classify(ins)
            if cl is None:
                continue
            cat, fl, _by = cl
            if cat == "fusion":
                for c, f in body_flops(_attr(ins.attrs, "calls"),
                                       stack + (name,)).items():
                    c = c if isinstance(c, tuple) else "fusion"
                    totals[c] = totals.get(c, 0) + f
                continue
            if cat in ("conv", "dot"):
                cat = (cat, _phase(ins.attrs) or "none")
            totals[cat] = totals.get(cat, 0) + fl
        flops_memo[name] = totals
        return totals

    phase_memo: Dict[str, frozenset] = {}

    def phases_of(ins, stack=()):
        """The phases an instruction and, for a fusion, its members
        (fusions inside it included) carry. What computes nothing has
        no say: a constant keeps the scope that first made it."""
        own = None if ins.opcode in _SKIP else _phase(ins.attrs)
        found = {own} if own else set()
        name = _attr(ins.attrs, "calls") if ins.opcode == "fusion" \
            else None
        if name in phase_memo:
            return found | phase_memo[name]
        if name is None or name in stack or name not in comps:
            return found
        inside = set()
        for member in comps[name]:
            inside |= phases_of(member, stack + (name,))
        phase_memo[name] = frozenset(inside)
        return found | inside

    def trip_count(ins):
        m = _TRIPS_RE.search(ins.attrs)
        if m:
            return int(m.group(1))
        cond = comps.get(_attr(ins.attrs, "condition"), ())
        consts = {i.name: i.operands.strip() for i in cond
                  if i.opcode == "constant"}
        for i in cond:
            # counting up from zero by one, as ``lax.scan`` and
            # ``fori_loop(0, n)`` lower
            if i.opcode == "compare" and "direction=LT" in i.attrs:
                for ref in _refs(i.operands):
                    if consts.get(ref, "").isdigit():
                        return int(consts[ref])
        return None

    agg = {c: {"flops": 0, "bytes": 0, "count": 0} for c in CATEGORIES}
    census: Dict[str, Dict[str, int]] = {}
    coll_ops: Dict[str, Dict[str, int]] = {}
    by_phase: Dict[str, int] = {}       # matrix FLOPs, product by product
    once = [0]

    def walk(comp, trips, stack):
        for ins in comps[comp]:
            if ins.opcode == "while":
                body = _attr(ins.attrs, "body")
                if body in comps and body not in stack:
                    n = trip_count(ins)
                    once[0] += n is None
                    walk(body, trips * (n or 1), stack + (comp,))
                continue
            cl = classify(ins)
            if cl is None:
                continue
            cat, fl, by = cl
            agg[cat]["bytes"] += by * trips
            agg[cat]["count"] += trips
            matrix = 0                  # convolutions and dots inside
            if cat == "collective":
                # per-opcode sub-buckets: an fsdp step's all-gather
                # (param gather before forward) and reduce-scatter (grad
                # shard-reduce) are distinguishable from the dp all-reduce
                sub = coll_ops.setdefault(ins.opcode,
                                          {"bytes": 0, "count": 0})
                sub["bytes"] += by * trips
                sub["count"] += trips
            if cat == "fusion":
                for c, f in body_flops(_attr(ins.attrs, "calls"),
                                       (comp,)).items():
                    if isinstance(c, tuple):
                        c, phase = c
                        matrix += f
                        by_phase[phase] = by_phase.get(phase, 0) + f * trips
                    else:
                        c = "fusion"
                    agg[c]["flops"] += f * trips
            else:
                agg[cat]["flops"] += fl * trips
                if cat in ("conv", "dot"):
                    matrix = fl
                    phase = _phase(ins.attrs) or "none"
                    by_phase[phase] = by_phase.get(phase, 0) + fl * trips
            found = phases_of(ins)
            row = census.setdefault(
                "+".join(p for p in PHASES if p in found) or "none",
                {"ops": 0, "flops": 0, "bytes": 0})
            row["ops"] += trips
            row["flops"] += matrix * trips
            row["bytes"] += by * trips

    walk(entry, 1, ())
    if coll_ops:
        agg["collective"]["by_op"] = coll_ops
    return ({c: v for c, v in agg.items()
             if v.get("count") or v.get("flops")}, census, by_phase,
            once[0])


def hlo_op_breakdown(hlo_text: str) -> Dict[str, dict]:
    """Parse optimized HLO text into ``{category: {"flops", "bytes",
    "count"}}`` over the program's top-level instructions
    (:func:`_analyze_hlo` has the rules). Fused computations contribute
    their body's conv/dot FLOPs to those categories and everything else
    to ``fusion``, whose bytes are the fusion's interface traffic. The
    per-category FLOPs sum to the reported total by construction —
    cross-check against ``cost_analysis()['flops']`` lives in the
    CompileRecord beside it."""
    return _analyze_hlo(hlo_text)[0]


def hlo_phase_census(hlo_text: str) -> Dict[str, dict]:
    """``{set: {"ops", "flops", "bytes"}}``: the program's top-level
    instructions grouped by the SET of phases they and, for a fusion,
    their members carry in ``op_name`` (``fwd``, ``recompute``, ``bwd``,
    ``update``, ``metric`` as the fused step scopes them; joined by
    ``+`` in that order, ``none`` where no member names one: copies and
    layout work the compiler added). ``flops`` are the convolutions' and
    dots' inside, ``bytes`` the instruction's operands and results: the
    least the chip moves for it, not a measured time."""
    return _analyze_hlo(hlo_text)[1]


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
    (CPU — no allocator, so ``limit_bytes`` and ``reserved_bytes``,
    what the runtime holds for compiled programs' temporaries, are
    None). One reading: in use and reserved are of the same moment. The
    live_arrays walk is per-device exact: a sharded array contributes
    only the bytes of its shards resident on ``device`` (an
    fsdp-sharded pack bills 1/fsdp per chip), never its GLOBAL
    ``nbytes`` — billing the whole pack to device 0 is precisely the
    accounting bug a sharded mesh exposes."""
    import jax

    try:
        dev = device if device is not None else jax.devices()[0]
    except Exception:
        return {"live_bytes": 0, "limit_bytes": None, "peak_bytes": None,
                "reserved_bytes": None, "source": "none"}
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
                "reserved_bytes": (int(ms["bytes_reserved"])
                                   if ms.get("bytes_reserved") is not None
                                   else None),
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
    return {"live_bytes": live, "limit_bytes": None, "peak_bytes": None,
            "reserved_bytes": None, "source": "live_arrays"}


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
