"""graftlint: AST static analysis for JAX hazards in this codebase.

PRs 4 and 5 burned satellite budget hand-fixing four recurring hazard
classes; this module turns those reviews into code (the reference
framework's dmlc-core lint + nightly-gate role, PAPER.md layer 0).
Four rule families:

``host-sync``
    In step-loop-reachable modules (engine, executor, fused_step,
    metric, io_pipeline) any host<->device synchronization — numpy
    conversion of a possibly-device value, ``.item()`` / ``.asnumpy()``
    / ``.tolist()`` / ``.block_until_ready()`` / ``jax.device_get``,
    ``float()``/``int()``/``bool()`` or Python truthiness on a value
    produced by a jnp/jax call — must carry an explicit
    ``# graft: host-sync`` annotation. A silent sync in the step loop
    leaves the device idle between dispatches (the benchmark's
    ``fit_host_gap_ms_per_step`` and idle share).

``donation``
    A name passed in a ``donate_argnums`` position of a jitted callable
    must not be read again in the same scope (the buffer is deleted —
    the read raises at run time, but only on configurations where
    donation is armed, which is how PR 5's aliasing bugs shipped).
    Suppress intentional reads with ``# graft: donated-ok``.

``tracer``
    Inside a function wrapped by ``jax.jit`` (decorator or call-site
    wrap in the same module): impure calls (``time.*``, ``np.random.*``,
    ``os.environ`` / ``getenv``, ``print``, ``open``) bake a value into
    the compiled artifact or silently re-execute at trace time only;
    Python ``if``/``while``/``for`` on a traced parameter raises a
    ``TracerBoolConversionError`` at run time — or worse, silently
    retraces per value when the parameter is marked static elsewhere.
    Suppress with ``# graft: traced-ok`` (e.g. documented
    static_argnums flow the analyzer cannot prove).

``env-registry``
    Every ``MXNET_TPU_*`` read must go through :mod:`mxnet_tpu.env`
    (``env.get``), whose declarations generate ``docs/env_vars.md`` —
    a raw ``os.environ`` / ``base.getenv`` read of an ``MXNET_TPU_*``
    literal is exactly how 6 knobs shipped undocumented. Reads through
    ``env.get`` of a name missing from the registry are also findings.
    Writes (staging a child process env) are out of scope. Suppress
    with ``# graft: env-ok``.

Annotations live in comments on the finding line or the line above::

    acc = np.asarray(dev_sum)   # graft: host-sync

Pre-existing accepted findings can be carried in a baseline file
(``tools/graftlint_baseline.json``): fingerprints are stable under
line-number drift (rule, file, enclosing scope, normalized source
line, occurrence index), so only *new* findings fail the tier-1 gate
(``tests/test_graftlint.py``). CLI: ``tools/graftlint.py``.
"""
from __future__ import annotations

import ast
import hashlib
import io
import json
import os
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = ["Finding", "Config", "analyze_source", "analyze_paths",
           "load_baseline", "save_baseline", "partition",
           "declared_env_names", "RULES"]

RULES = ("host-sync", "donation", "tracer", "env-registry")

# Rule id -> comment tag that suppresses it. ``# graft: <tag>``.
SUPPRESS_TAGS = {
    "host-sync": "host-sync",
    "donation": "donated-ok",
    "tracer": "traced-ok",
    "env-registry": "env-ok",
}

# Default step-loop-reachable module set for the host-sync rule: code a
# training step executes per batch. Matched on file basename.
STEP_LOOP_FILES = frozenset({
    "engine.py", "executor.py", "fused_step.py", "metric.py",
    "io_pipeline.py",
})

_NP_CONVERT = frozenset({
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "_np.asarray", "_np.array", "np.ascontiguousarray", "np.asscalar",
})
_SYNC_METHODS = frozenset({
    "item", "tolist", "asnumpy", "block_until_ready",
})
_DEVICE_GET = frozenset({"jax.device_get", "device_get"})

_IMPURE_EXACT = frozenset({
    "time.time", "time.perf_counter", "time.monotonic", "time.time_ns",
    "time.sleep", "os.getenv", "os.environ.get", "getenv", "print",
    "input", "open", "id",
})
_IMPURE_PREFIX = ("np.random.", "numpy.random.", "random.",
    "datetime.datetime.")

_ENV_READERS = frozenset({"os.environ.get", "os.getenv", "environ.get",
                          "getenv"})
_ENV_REGISTRY_READERS = frozenset({"env.get", "_env.get", "env.is_set",
                                   "_env.is_set", "env.var", "_env.var"})


class Finding:
    """One rule violation at a source location."""

    __slots__ = ("rule", "path", "line", "col", "scope", "message",
                 "snippet", "fingerprint")

    def __init__(self, rule, path, line, col, scope, message, snippet,
                 fingerprint=""):
        self.rule = rule
        self.path = path
        self.line = line
        self.col = col
        self.scope = scope
        self.message = message
        self.snippet = snippet
        self.fingerprint = fingerprint

    def to_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "scope": self.scope, "message": self.message,
                "snippet": self.snippet, "fingerprint": self.fingerprint}

    def __repr__(self):
        return "%s:%d: [%s] %s" % (self.path, self.line, self.rule,
                                   self.message)


class Config:
    """Analyzer configuration; defaults match this repository."""

    def __init__(self, step_loop_files: Optional[Iterable[str]] = None,
                 declared_env: Optional[Iterable[str]] = None,
                 rules: Optional[Iterable[str]] = None):
        self.step_loop_files = frozenset(
            step_loop_files if step_loop_files is not None
            else STEP_LOOP_FILES)
        # None -> resolved lazily from mxnet_tpu/env.py next to this
        # package (pure AST parse; the analyzer never imports the tree
        # it lints)
        self.declared_env = (frozenset(declared_env)
                             if declared_env is not None else None)
        self.rules = frozenset(rules if rules is not None else RULES)

    def env_names(self) -> frozenset:
        if self.declared_env is None:
            self.declared_env = frozenset(declared_env_names())
        return self.declared_env


def declared_env_names(env_path: Optional[str] = None) -> Set[str]:
    """Names declared in mxnet_tpu/env.py, by AST (no import)."""
    if env_path is None:
        env_path = os.path.join(os.path.dirname(__file__), os.pardir,
                                "env.py")
    with open(env_path) as f:
        tree = ast.parse(f.read())
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and _dotted(node.func) in ("declare", "env.declare",
                                           "_env.declare") \
                and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            names.add(node.args[0].value)
    return names


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _dotted(node) -> str:
    """'jax.numpy.asarray' for an Attribute/Name chain, '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _comment_tags(source: str) -> Dict[int, Set[str]]:
    """lineno -> set of ``# graft: tag[, tag]`` annotation tags."""
    tags: Dict[int, Set[str]] = {}
    try:
        toks = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in toks:
            if tok.type != tokenize.COMMENT:
                continue
            text = tok.string.lstrip("#").strip()
            if not text.startswith("graft:"):
                continue
            found = {t.strip() for t in text[len("graft:"):].split(",")}
            tags.setdefault(tok.start[0], set()).update(t for t in found
                                                        if t)
    except tokenize.TokenError:
        pass
    return tags


def _names_in(node) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _scope_walk(scope):
    """Walk a scope's nodes WITHOUT descending into nested function
    definitions (each nested def is analyzed as its own scope)."""
    todo = list(ast.iter_child_nodes(scope))
    while todo:
        n = todo.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            todo.extend(ast.iter_child_nodes(n))


def _truthy_value_names(test) -> Set[str]:
    """Names whose runtime VALUE a test converts to a Python bool:
    bare names, `not x` / and-or chains of them, and value comparisons
    (`x > 0`). Identity/membership tests (`x is None`, `k in d`) and
    names buried inside calls/attribute metadata (`x.dtype == f0`,
    `len(xs)`, `getattr(x, ...)`) do not sync and are excluded."""
    out: Set[str] = set()
    if isinstance(test, ast.Name):
        out.add(test.id)
    elif isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        out |= _truthy_value_names(test.operand)
    elif isinstance(test, ast.BoolOp):
        for v in test.values:
            out |= _truthy_value_names(v)
    elif isinstance(test, ast.Compare):
        if all(not isinstance(o, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
               for o in test.ops):
            for operand in [test.left] + list(test.comparators):
                if isinstance(operand, ast.Name):
                    out.add(operand.id)
    return out


def _scopes(tree) -> List[Tuple[str, ast.AST]]:
    """(qualname, node) for the module and every (async) function, the
    finding-scope granularity fingerprints key on."""
    out: List[Tuple[str, ast.AST]] = [("<module>", tree)]

    def visit(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = prefix + child.name
                out.append((q, child))
                visit(child, q + ".")
            elif isinstance(child, ast.ClassDef):
                visit(child, prefix + child.name + ".")
            else:
                visit(child, prefix)

    visit(tree, "")
    return out


def _enclosing_scope(scopes, lineno) -> str:
    """Innermost function qualname containing ``lineno``."""
    best = "<module>"
    best_span = None
    for q, node in scopes:
        if q == "<module>":
            continue
        end = getattr(node, "end_lineno", node.lineno)
        if node.lineno <= lineno <= end:
            span = end - node.lineno
            if best_span is None or span <= best_span:
                best, best_span = q, span
    return best


class _Module:
    """Parsed module + everything the rules share."""

    def __init__(self, source: str, path: str, config: Config):
        self.source = source
        self.path = path
        self.config = config
        self.tree = ast.parse(source)
        self.lines = source.splitlines()
        self.tags = _comment_tags(source)
        self.scopes = _scopes(self.tree)
        self.basename = os.path.basename(path)

    def snippet(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def suppressed(self, rule: str, lineno: int) -> bool:
        tag = SUPPRESS_TAGS[rule]
        for ln in (lineno, lineno - 1):
            if tag in self.tags.get(ln, ()):
                return True
        return False

    def finding(self, rule: str, node, message: str) -> Optional[Finding]:
        line = getattr(node, "lineno", 1)
        if self.suppressed(rule, line):
            return None
        return Finding(rule, self.path, line,
                       getattr(node, "col_offset", 0),
                       _enclosing_scope(self.scopes, line), message,
                       self.snippet(line))


# ---------------------------------------------------------------------------
# rule: host-sync
# ---------------------------------------------------------------------------

def _device_tainted_names(scope) -> Set[str]:
    """Names assigned (anywhere in this scope) from a jnp./jax. call or
    from another module's ``._data`` device buffer — the local-dataflow
    approximation of 'this is a device value'."""
    tainted: Set[str] = set()

    def value_is_device(v) -> bool:
        if isinstance(v, ast.Call):
            d = _dotted(v.func)
            return d.startswith(("jnp.", "jax.")) and not d.startswith(
                "jax.tree_util")
        if isinstance(v, ast.Attribute):
            return v.attr == "_data"
        if isinstance(v, ast.BinOp):
            return value_is_device(v.left) or value_is_device(v.right)
        if isinstance(v, ast.Name):
            return v.id in tainted
        return False

    for node in _scope_walk(scope):
        if isinstance(node, ast.Assign) and value_is_device(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    tainted.add(t.id)
        elif isinstance(node, ast.AugAssign) \
                and isinstance(node.target, ast.Name) \
                and value_is_device(node.value):
            tainted.add(node.target.id)
    return tainted


def _check_host_sync(mod: _Module) -> List[Finding]:
    if mod.basename not in mod.config.step_loop_files:
        return []
    findings: List[Finding] = []

    def emit(node, msg):
        f = mod.finding("host-sync", node, msg)
        if f is not None:
            findings.append(f)

    for qual, scope in mod.scopes:
        tainted = _device_tainted_names(scope)
        for node in _scope_walk(scope):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d in _NP_CONVERT and node.args and isinstance(
                        node.args[0], (ast.Name, ast.Attribute,
                                       ast.Subscript)):
                    emit(node, "%s() in step-loop code syncs (or "
                         "copies to) the host" % d)
                elif d in _DEVICE_GET:
                    emit(node, "jax.device_get() in step-loop code "
                         "is a blocking device->host fetch")
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _SYNC_METHODS \
                        and not node.args:
                    emit(node, ".%s() in step-loop code blocks on "
                         "the device" % node.func.attr)
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in ("float", "int", "bool") \
                        and len(node.args) == 1:
                    a = node.args[0]
                    if (isinstance(a, ast.Name) and a.id in tainted) \
                            or (isinstance(a, ast.Attribute)
                                and a.attr == "_data"):
                        emit(node, "%s() on a device value forces a "
                             "host sync" % node.func.id)
            elif isinstance(node, (ast.If, ast.While)):
                test_names = _truthy_value_names(node.test) & tainted
                if test_names:
                    emit(node, "truthiness of device value%s %s "
                         "forces a host sync"
                         % ("s" if len(test_names) > 1 else "",
                            ", ".join(sorted(test_names))))
    return findings


# ---------------------------------------------------------------------------
# rule: donation
# ---------------------------------------------------------------------------

def _donate_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """donate_argnums positions of a jax.jit(...) call, or None."""
    d = _dotted(call.func)
    if not (d.endswith("jax.jit") or d == "jit"
            or d.endswith("functools.partial") or d == "partial"):
        return None
    if d.endswith("partial"):
        # partial(jax.jit, donate_argnums=...) — only with jax.jit inside
        if not (call.args and _dotted(call.args[0]).endswith("jit")):
            return None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, ast.Tuple):
                out = []
                for e in v.elts:
                    if isinstance(e, ast.Constant) \
                            and isinstance(e.value, int):
                        out.append(e.value)
                return tuple(out)
            return ()   # dynamic (e.g. conditional) — can't track
    return None


def _donation_events(node, events) -> None:
    """Append ``(kind, node)`` tuples in approximate execution order:
    assignment values before their targets, call arguments before the
    call itself. Nested function/class bodies are separate scopes and
    are not descended into."""
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                         ast.Lambda, ast.ClassDef)):
        return
    if isinstance(node, ast.Assign):
        _donation_events(node.value, events)
        for t in node.targets:
            _donation_events(t, events)
        return
    if isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        if node.value is not None:
            _donation_events(node.value, events)
        _donation_events(node.target, events)
        return
    if isinstance(node, ast.For):
        _donation_events(node.iter, events)
        _donation_events(node.target, events)
        for child in node.body + node.orelse:
            _donation_events(child, events)
        return
    if isinstance(node, ast.Name):
        events.append(("store" if isinstance(node.ctx,
                                             (ast.Store, ast.Del))
                       else "load", node))
        return
    for child in ast.iter_child_nodes(node):
        _donation_events(child, events)
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        events.append(("call", node))


def _check_donation(mod: _Module) -> List[Finding]:
    findings: List[Finding] = []

    for qual, scope in mod.scopes:
        body = scope.body if hasattr(scope, "body") else []
        # jitted-callable names -> donated positions, within this scope
        donated_fns: Dict[str, Tuple[int, ...]] = {}
        # decorated defs in this scope with donate_argnums
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in stmt.decorator_list:
                    if isinstance(dec, ast.Call):
                        pos = _donate_positions(dec)
                        if pos:
                            donated_fns[stmt.name] = pos
        for node in _scope_walk(scope):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call):
                pos = _donate_positions(node.value)
                if pos:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            donated_fns[t.id] = pos
        # (kind, node) in execution order — an assignment's value runs
        # before its targets store, a call's args load before the call;
        # source-position order gets both wrong for
        # ``_, p, _ = jit_step(p, ...)``.
        events: list = []
        for stmt in body:
            _donation_events(stmt, events)

        dead: Dict[str, int] = {}   # name -> line it was donated at
        for kind, node in events:
            if kind == "call":
                fn = node.func.id
                pos = donated_fns.get(fn)
                if pos:
                    for i in pos:
                        if i < len(node.args) \
                                and isinstance(node.args[i], ast.Name):
                            dead[node.args[i].id] = node.lineno
            elif kind == "store":
                dead.pop(node.id, None)
            elif kind == "load":
                at = dead.get(node.id)
                if at is not None:
                    f = mod.finding(
                        "donation", node,
                        "'%s' was donated to a jit at line %d and read "
                        "afterwards: the buffer is deleted on donating "
                        "backends" % (node.id, at))
                    if f is not None:
                        findings.append(f)
                    dead.pop(node.id, None)   # report once per donation
    return findings


# ---------------------------------------------------------------------------
# rule: tracer
# ---------------------------------------------------------------------------

def _jit_static_names(call: Optional[ast.Call],
                      fndef) -> Set[str]:
    """Parameter names marked static in a jax.jit call, best effort."""
    static: Set[str] = set()
    if call is None:
        return static
    params = [a.arg for a in fndef.args.args]
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            vals = v.elts if isinstance(v, ast.Tuple) else [v]
            for e in vals:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    static.add(e.value)
        elif kw.arg == "static_argnums":
            v = kw.value
            vals = v.elts if isinstance(v, ast.Tuple) else [v]
            for e in vals:
                if isinstance(e, ast.Constant) \
                        and isinstance(e.value, int) \
                        and e.value < len(params):
                    static.add(params[e.value])
    return static


def _jitted_defs(mod: _Module):
    """(fndef, jit_call_or_None) for every function the module wraps in
    jax.jit — by decorator, or by a call-site wrap of its name."""
    wrapped_names: Dict[str, ast.Call] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            if (d.endswith("jax.jit") or d == "jit") and node.args \
                    and isinstance(node.args[0], ast.Name):
                wrapped_names[node.args[0].id] = node
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        jit_call = None
        jitted = False
        for dec in node.decorator_list:
            d = _dotted(dec)
            if d.endswith("jax.jit") or d == "jit":
                jitted = True
            elif isinstance(dec, ast.Call):
                dd = _dotted(dec.func)
                if dd.endswith("jax.jit") or dd == "jit":
                    jitted, jit_call = True, dec
                elif dd.endswith("partial") and dec.args \
                        and _dotted(dec.args[0]).endswith("jit"):
                    jitted, jit_call = True, dec
        if not jitted and node.name in wrapped_names:
            jitted, jit_call = True, wrapped_names[node.name]
        if jitted:
            yield node, jit_call


def _check_tracer(mod: _Module) -> List[Finding]:
    findings: List[Finding] = []

    def emit(node, msg):
        f = mod.finding("tracer", node, msg)
        if f is not None:
            findings.append(f)

    for fndef, jit_call in _jitted_defs(mod):
        params = {a.arg for a in fndef.args.args
                  if a.arg not in ("self", "cls")}
        params -= _jit_static_names(jit_call, fndef)
        for stmt in fndef.body:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Call):
                    d = _dotted(node.func)
                    if d in _IMPURE_EXACT \
                            or d.startswith(_IMPURE_PREFIX):
                        emit(node, "impure call %s() inside a jitted "
                             "function runs at trace time only (or "
                             "bakes a stale value into the compiled "
                             "artifact)" % d)
                elif isinstance(node, (ast.If, ast.While)):
                    hit = _truthy_value_names(node.test) & params
                    if hit:
                        emit(node, "Python %s on traced value%s %s: "
                             "use lax.cond/jnp.where (or mark the "
                             "argument static)"
                             % ("if" if isinstance(node, ast.If)
                                else "while",
                                "s" if len(hit) > 1 else "",
                                ", ".join(sorted(hit))))
                elif isinstance(node, ast.For):
                    hit = ({node.iter.id}
                           if isinstance(node.iter, ast.Name) else
                           set()) & params
                    if hit:
                        emit(node, "Python for-loop over traced value%s "
                             "%s unrolls (or fails) at trace time: use "
                             "lax.scan/fori_loop"
                             % ("s" if len(hit) > 1 else "",
                                ", ".join(sorted(hit))))
    return findings


# ---------------------------------------------------------------------------
# rule: env-registry
# ---------------------------------------------------------------------------

def _check_env_registry(mod: _Module) -> List[Finding]:
    if mod.basename == "env.py":
        return []
    findings: List[Finding] = []
    declared = mod.config.env_names()

    def emit(node, msg):
        f = mod.finding("env-registry", node, msg)
        if f is not None:
            findings.append(f)

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str) \
                    and node.args[0].value.startswith("MXNET_TPU_"):
                name = node.args[0].value
                if d in _ENV_READERS or d.endswith(".environ.get") \
                        or d.endswith(".getenv"):
                    emit(node, "%s(%r) bypasses the env registry: "
                         "declare in mxnet_tpu/env.py and read via "
                         "env.get" % (d, name))
                elif d in _ENV_REGISTRY_READERS and name not in declared:
                    emit(node, "%s(%r): name is not declared in "
                         "mxnet_tpu/env.py" % (d, name))
        elif isinstance(node, ast.Subscript) \
                and isinstance(node.ctx, ast.Load) \
                and _dotted(node.value) in ("os.environ", "environ"):
            sl = node.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value, str) \
                    and sl.value.startswith("MXNET_TPU_"):
                emit(node, "os.environ[%r] read bypasses the env "
                     "registry" % sl.value)
    return findings


_RULE_FNS = {
    "host-sync": _check_host_sync,
    "donation": _check_donation,
    "tracer": _check_tracer,
    "env-registry": _check_env_registry,
}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _fingerprint(findings: List[Finding]) -> None:
    """Assign stable fingerprints: line numbers are excluded so pure
    drift doesn't invalidate a baseline; an occurrence index
    disambiguates identical lines in one scope."""
    seen: Dict[Tuple, int] = {}
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.col)):
        key = (f.rule, f.path, f.scope, f.snippet)
        k = seen.get(key, 0)
        seen[key] = k + 1
        raw = "|".join((f.rule, f.path, f.scope, f.snippet, str(k)))
        f.fingerprint = hashlib.sha1(raw.encode()).hexdigest()[:16]


def analyze_source(source: str, path: str,
                   config: Optional[Config] = None) -> List[Finding]:
    """Run every configured rule over one module's source."""
    config = config or Config()
    try:
        mod = _Module(source, path, config)
    except SyntaxError as e:
        return [Finding("parse-error", path, e.lineno or 1, 0, "<module>",
                        "syntax error: %s" % e.msg, "",
                        fingerprint="parse:%s" % path)]
    findings: List[Finding] = []
    for rule, fn in _RULE_FNS.items():
        if rule in config.rules:
            findings.extend(fn(mod))
    _fingerprint(findings)
    findings.sort(key=lambda f: (f.path, f.line, f.col))
    return findings


def iter_py_files(paths: Sequence[str]) -> List[str]:
    out = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git", "_native")]
                out.extend(os.path.join(root, f) for f in files
                           if f.endswith(".py"))
    return sorted(set(out))


def analyze_paths(paths: Sequence[str],
                  config: Optional[Config] = None,
                  root: Optional[str] = None) -> List[Finding]:
    """Analyze every .py under ``paths``; finding paths are relative to
    ``root`` (default: cwd) so baselines are machine-independent."""
    config = config or Config()
    root = root or os.getcwd()
    findings: List[Finding] = []
    for fpath in iter_py_files(paths):
        with open(fpath, encoding="utf-8") as f:
            src = f.read()
        rel = os.path.relpath(fpath, root).replace(os.sep, "/")
        findings.extend(analyze_source(src, rel, config))
    findings.sort(key=lambda f: (f.path, f.line, f.col))
    return findings


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str) -> Set[str]:
    """Accepted-finding fingerprints from a baseline file."""
    with open(path) as f:
        data = json.load(f)
    return {e["fingerprint"] for e in data.get("findings", [])}


def save_baseline(path: str, findings: Sequence[Finding]) -> None:
    data = {
        "comment": "graftlint accepted findings; regenerate with "
                   "`python tools/graftlint.py --write-baseline "
                   "--baseline %s <paths>`" % os.path.basename(path),
        "version": 1,
        "findings": [f.to_dict() for f in findings],
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")


def partition(findings: Sequence[Finding],
              baseline: Set[str]) -> Tuple[List[Finding], List[Finding]]:
    """(new, accepted) split against baseline fingerprints."""
    new = [f for f in findings if f.fingerprint not in baseline]
    old = [f for f in findings if f.fingerprint in baseline]
    return new, old
