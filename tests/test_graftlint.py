"""graftlint static analysis + runtime sanitizers: rule-family
fixtures (good/bad pairs), annotation + baseline suppression, the
whole-tree tier-1 gate, env-registry/docs drift, and seeded runtime
violations proving each sanitizer fires."""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import env, telemetry
from mxnet_tpu.analysis import graftlint, sanitizers
from mxnet_tpu.analysis.sanitizers import (DonationSanitizer,
                                           RetraceSanitizer,
                                           SanitizerError)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a config with a known env universe so fixture tests don't depend on
# the real registry's contents
CFG = graftlint.Config(declared_env={"MXNET_TPU_DECLARED"})


def _rules(findings):
    return sorted({f.rule for f in findings})


def _lint(src, path="pkg/engine.py", rules=None):
    cfg = graftlint.Config(declared_env={"MXNET_TPU_DECLARED"},
                           rules=rules)
    return graftlint.analyze_source(src, path, cfg)


# ---------------------------------------------------------------------------
# host-sync rule
# ---------------------------------------------------------------------------

def test_host_sync_flags_numpy_conversion_in_step_loop_file():
    src = "def step(x):\n    return np.asarray(x)\n"
    bad = _lint(src, "pkg/engine.py")
    assert _rules(bad) == ["host-sync"]
    # same code outside the step-loop module set is fine
    assert _lint(src, "pkg/visualization.py") == []


def test_host_sync_flags_sync_methods_and_device_get():
    for call in ("x.item()", "x.tolist()", "x.asnumpy()",
                 "x.block_until_ready()", "jax.device_get(x)"):
        src = "def step(x):\n    return %s\n" % call
        assert _rules(_lint(src)) == ["host-sync"], call


def test_host_sync_flags_float_and_truthiness_on_device_value():
    src = ("def step(a):\n"
           "    loss = jnp.mean(a)\n"
           "    return float(loss)\n")
    assert _rules(_lint(src)) == ["host-sync"]
    src = ("def step(a):\n"
           "    ok = jnp.all(a)\n"
           "    if ok:\n"
           "        return 1\n")
    assert _rules(_lint(src)) == ["host-sync"]


def test_host_sync_ignores_host_only_values():
    src = ("def step(n):\n"
           "    m = n + 1\n"
           "    if m:\n"
           "        return float(m)\n")
    assert _lint(src) == []
    # metadata comparisons on device values don't sync
    src = ("def step(a):\n"
           "    v = jnp.mean(a)\n"
           "    if v is None:\n"
           "        return 0\n"
           "    return v\n")
    assert _lint(src) == []


def test_host_sync_annotation_suppresses():
    src = ("def step(x):\n"
           "    return np.asarray(x)  # graft: host-sync\n")
    assert _lint(src) == []
    src = ("def step(x):\n"
           "    # graft: host-sync\n"
           "    return np.asarray(x)\n")
    assert _lint(src) == []


# ---------------------------------------------------------------------------
# donation rule
# ---------------------------------------------------------------------------

def test_donation_flags_read_after_donating_call():
    src = ("fn = jax.jit(step, donate_argnums=(0,))\n"
           "out = fn(params, batch)\n"
           "print(params)\n")
    found = _lint(src, "pkg/train.py")
    assert _rules(found) == ["donation"]
    assert "donated" in found[0].message


def test_donation_reassignment_kills_the_hazard():
    # the canonical donated-step loop: the name is rebound to the NEW
    # buffer by the same statement that donates the old one
    src = ("fn = jax.jit(step, donate_argnums=(0,))\n"
           "_, params = fn(params, batch)\n"
           "_, params = fn(params, batch)\n"
           "print(params)\n")
    assert _lint(src, "pkg/train.py") == []


def test_donation_decorated_def_and_annotation():
    src = ("@functools.partial(jax.jit, donate_argnums=(1,))\n"
           "def fn(a, b):\n"
           "    return a + b\n"
           "out = fn(x, y)\n"
           "print(y)\n")
    assert _rules(_lint(src, "pkg/train.py")) == ["donation"]
    src = src.replace("print(y)", "print(y)  # graft: donated-ok")
    assert _lint(src, "pkg/train.py") == []


# ---------------------------------------------------------------------------
# tracer rule
# ---------------------------------------------------------------------------

def test_tracer_flags_impure_call_in_jitted_fn():
    src = ("@jax.jit\n"
           "def fn(a):\n"
           "    t = time.time()\n"
           "    return a * t\n")
    found = _lint(src, "pkg/anything.py")
    assert _rules(found) == ["tracer"]


def test_tracer_flags_python_branch_on_traced_param():
    src = ("@jax.jit\n"
           "def fn(a):\n"
           "    if a:\n"
           "        return a + 1\n"
           "    return a\n")
    assert _rules(_lint(src, "pkg/x.py")) == ["tracer"]


def test_tracer_callsite_wrap_and_suppressions():
    src = ("def fn(a):\n"
           "    return a * np.random.rand()\n"
           "fn = jax.jit(fn)\n")
    assert _rules(_lint(src, "pkg/x.py")) == ["tracer"]
    src = ("def fn(a):\n"
           "    return a * np.random.rand()  # graft: traced-ok\n"
           "fn = jax.jit(fn)\n")
    assert _lint(src, "pkg/x.py") == []
    # un-jitted functions may branch and be impure
    src = ("def fn(a):\n"
           "    if a:\n"
           "        return time.time()\n")
    assert _lint(src, "pkg/x.py") == []


def test_tracer_static_args_may_branch():
    src = ("@functools.partial(jax.jit, static_argnums=(1,))\n"
           "def fn(a, flag):\n"
           "    if flag:\n"
           "        return a + 1\n"
           "    return a\n")
    assert _lint(src, "pkg/x.py") == []


# ---------------------------------------------------------------------------
# env-registry rule
# ---------------------------------------------------------------------------

def test_env_registry_flags_raw_reads():
    for read in ('os.environ.get("MXNET_TPU_FOO")',
                 'os.getenv("MXNET_TPU_FOO")',
                 'getenv("MXNET_TPU_FOO", 3)',
                 'os.environ["MXNET_TPU_FOO"]'):
        src = "x = %s\n" % read
        assert _rules(_lint(src, "pkg/x.py")) == ["env-registry"], read


def test_env_registry_ignores_non_prefix_and_writes():
    src = ('a = os.environ.get("HOME")\n'
           'os.environ["MXNET_TPU_FOO"] = "1"\n')
    assert _lint(src, "pkg/x.py") == []


def test_env_registry_checks_declared_names():
    assert _lint('v = env.get("MXNET_TPU_DECLARED")\n', "pkg/x.py") == []
    found = _lint('v = env.get("MXNET_TPU_MISSING")\n', "pkg/x.py")
    assert _rules(found) == ["env-registry"]
    src = ('# graft: env-ok\n'
           'v = os.environ.get("MXNET_TPU_FOO")\n')
    assert _lint(src, "pkg/x.py") == []


def test_declared_env_names_parses_real_registry():
    names = graftlint.declared_env_names(
        os.path.join(ROOT, "mxnet_tpu", "env.py"))
    assert names == set(env.declared())
    assert "MXNET_TPU_FUSED_STEP" in names


# ---------------------------------------------------------------------------
# baseline + fingerprints
# ---------------------------------------------------------------------------

def test_fingerprints_stable_under_line_drift():
    src = "def step(x):\n    return np.asarray(x)\n"
    f1 = _lint(src)[0]
    f2 = _lint("import os\n\n\n" + src)[0]
    assert f1.line != f2.line
    assert f1.fingerprint == f2.fingerprint
    # ...but distinct duplicate occurrences stay distinct
    dup = ("def step(x):\n"
           "    a = np.asarray(x)\n"
           "    b = np.asarray(x)\n")
    fps = [f.fingerprint for f in _lint(dup)]
    assert len(fps) == 2 and len(set(fps)) == 2


def test_baseline_roundtrip_and_partition(tmp_path):
    src = "def step(x):\n    return np.asarray(x)\n"
    findings = _lint(src)
    bl = tmp_path / "baseline.json"
    graftlint.save_baseline(str(bl), findings)
    accepted = graftlint.load_baseline(str(bl))
    new, old = graftlint.partition(findings, accepted)
    assert new == [] and len(old) == 1
    # an unrelated finding is NOT covered
    other = _lint("def step(y):\n    return y.item()\n")
    new, _ = graftlint.partition(other, accepted)
    assert len(new) == 1
    data = json.loads(bl.read_text())
    assert data["version"] == 1


def test_parse_error_is_reported_not_raised():
    found = graftlint.analyze_source("def broken(:\n", "pkg/x.py", CFG)
    assert len(found) == 1 and found[0].rule == "parse-error"


# ---------------------------------------------------------------------------
# the tier-1 gate: the shipped tree is clean against the shipped baseline
# ---------------------------------------------------------------------------

def test_repo_tree_has_no_unbaselined_findings():
    findings = graftlint.analyze_paths(
        [os.path.join(ROOT, "mxnet_tpu"), os.path.join(ROOT, "tools"),
         os.path.join(ROOT, "chip_smoke.py")], root=ROOT)
    baseline = graftlint.load_baseline(
        os.path.join(ROOT, "tools", "graftlint_baseline.json"))
    new, _ = graftlint.partition(findings, baseline)
    assert new == [], "new graftlint findings:\n%s" % "\n".join(
        repr(f) for f in new)


def test_env_docs_in_sync_with_registry():
    assert env.sync_docs(os.path.join(ROOT, "docs", "env_vars.md"),
                         check=True), (
        "docs/env_vars.md is out of sync with mxnet_tpu/env.py — run "
        "`python tools/graftlint.py --write-env-docs`")


# ---------------------------------------------------------------------------
# env registry semantics
# ---------------------------------------------------------------------------

def test_env_get_reads_declared_default_and_coerces(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_FEED_DEPTH", raising=False)
    assert env.get("MXNET_TPU_FEED_DEPTH") == 0
    monkeypatch.setenv("MXNET_TPU_FEED_DEPTH", "3")
    assert env.get("MXNET_TPU_FEED_DEPTH") == 3
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "true")
    assert env.get("MXNET_TPU_FUSED_STEP") is True
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "0")
    assert env.get("MXNET_TPU_FUSED_STEP") is False


def test_env_get_dynamic_default_override(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_DECODE_PROCS", raising=False)
    assert env.get("MXNET_TPU_DECODE_PROCS", default=7) == 7
    monkeypatch.setenv("MXNET_TPU_DECODE_PROCS", "2")
    assert env.get("MXNET_TPU_DECODE_PROCS", default=7) == 2


def test_env_undeclared_read_raises():
    with pytest.raises(KeyError, match="not declared"):
        env.get("MXNET_TPU_NOT_A_THING")
    with pytest.raises(ValueError, match="declared twice"):
        env.declare("MXNET_TPU_FUSED_STEP", bool, False, "dup")


# ---------------------------------------------------------------------------
# runtime sanitizers
# ---------------------------------------------------------------------------

def test_sanitize_parsing(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_SANITIZE", raising=False)
    assert sanitizers.enabled_kinds() == frozenset()
    monkeypatch.setenv("MXNET_TPU_SANITIZE", "transfer, donation")
    assert sanitizers.enabled_kinds() == {"transfer", "donation"}
    monkeypatch.setenv("MXNET_TPU_SANITIZE", "all")
    assert sanitizers.enabled_kinds() == set(sanitizers.KINDS)
    monkeypatch.setenv("MXNET_TPU_SANITIZE", "typo")
    with pytest.raises(SanitizerError, match="unknown sanitizer"):
        sanitizers.enabled_kinds()


def test_transfer_sanitizer_catches_implicit_transfer(monkeypatch):
    """Seeded violation: a numpy array leaking into a jitted dispatch
    under the armed guard raises; the explicit device_put path and an
    intentional_transfer window stay allowed."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("MXNET_TPU_SANITIZE", "transfer")
    fn = jax.jit(lambda a: a * 2)
    host = np.ones((4,), np.float32)
    with sanitizers.step_guard():
        with pytest.raises(Exception) as ei:
            fn(host).block_until_ready()  # graft: host-sync
        assert sanitizers.is_transfer_guard_error(ei.value)
        # explicit transfers are the sanctioned API and stay legal
        dev = jax.device_put(host)
        fn(dev).block_until_ready()  # graft: host-sync
        # ...and a reviewed window re-allows implicit ones
        with sanitizers.intentional_transfer():
            fn(host).block_until_ready()  # graft: host-sync
        # the guard is restored after the window closes
        with pytest.raises(Exception):
            fn(host)
    # disarmed: no guard at all
    monkeypatch.delenv("MXNET_TPU_SANITIZE", raising=False)
    with sanitizers.step_guard():
        fn(host).block_until_ready()  # graft: host-sync


def test_retrace_sanitizer_fires_after_warmup(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_SANITIZE", "retrace")
    san = RetraceSanitizer(warmup=2)
    san.check(1)   # warmup step 1 (first trace)
    san.check(2)   # warmup step 2 (shape-bucket retrace: allowed)
    san.check(2)   # steady state, no growth
    telemetry.reset()
    telemetry.enable()
    try:
        with pytest.raises(SanitizerError, match="retrace sanitizer"):
            san.check(3)
        assert telemetry.peek("sanitizer.trips") == 1
        assert telemetry.peek("sanitizer.trips.retrace") == 1
    finally:
        telemetry.reset()
        telemetry.disable()


def test_retrace_sanitizer_warmup_from_env(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_SANITIZE_WARMUP", "5")
    assert RetraceSanitizer().warmup == 5


def test_donation_sanitizer_passes_on_real_donation():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda a: a + 1, donate_argnums=(0,))
    x = jnp.ones((8,), jnp.float32)
    y = fn(x)
    y.block_until_ready()  # graft: host-sync
    # CPU jax honors donation: the input buffer is consumed
    DonationSanitizer.check("test dispatch", [x])


def test_donation_sanitizer_raises_on_alive_buffer():
    """Seeded violation: claim a live buffer was donated."""
    import jax.numpy as jnp

    alive = jnp.ones((8,), jnp.float32)
    telemetry.reset()
    telemetry.enable()
    try:
        with pytest.raises(SanitizerError, match="donation sanitizer"):
            DonationSanitizer.check("test dispatch", [alive])
        assert telemetry.peek("sanitizer.trips.donation") == 1
    finally:
        telemetry.reset()
        telemetry.disable()


# ---------------------------------------------------------------------------
# fit()-level integration: the armed guard + the fused step
# ---------------------------------------------------------------------------

def _fused_fit(monkeypatch, callback=None, nbatches=3, num_epoch=1):
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.module import Module

    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=8, name="fc1")
    net = sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    X = rng.randn(8 * nbatches, 6).astype(np.float32)
    y = rng.randint(0, 8, size=8 * nbatches).astype(np.float32)
    data = mx.io.NDArrayIter(X, y, batch_size=8)
    mod = Module(net, context=mx.cpu())
    mod.fit(data, num_epoch=num_epoch, optimizer="sgd",
            optimizer_params={"learning_rate": 0.01},
            batch_end_callback=callback)
    assert mod._fused_step_active
    return mod


def test_fused_fit_clean_under_transfer_guard(monkeypatch):
    """The whole fused path — marshalling, dispatch, metric fold,
    metric.get() — runs under the armed guard without a single
    unsanctioned transfer."""
    monkeypatch.setenv("MXNET_TPU_SANITIZE", "transfer")
    _fused_fit(monkeypatch)


def test_fused_fit_guard_catches_seeded_violation(monkeypatch):
    """A step-loop callback smuggling a host array into a device op
    fails the batch it happens on, and the trip is counted."""
    import jax

    monkeypatch.setenv("MXNET_TPU_SANITIZE", "transfer")
    jit_mul = jax.jit(lambda a: a * 2)

    def bad_callback(param):
        jit_mul(np.ones((2,), np.float32))

    telemetry.reset()
    telemetry.enable()
    try:
        with pytest.raises(Exception) as ei:
            _fused_fit(monkeypatch, callback=bad_callback)
        assert sanitizers.is_transfer_guard_error(ei.value)
        assert telemetry.peek("sanitizer.trips.transfer") == 1
    finally:
        telemetry.reset()
        telemetry.disable()


def test_fused_fit_retrace_sanitizer_end_to_end(monkeypatch):
    """Same-shape batches never retrace after warmup: a fused fit with
    the retrace sanitizer armed (warmup 1) completes."""
    monkeypatch.setenv("MXNET_TPU_SANITIZE", "retrace")
    monkeypatch.setenv("MXNET_TPU_SANITIZE_WARMUP", "1")
    _fused_fit(monkeypatch, nbatches=4)


def test_fused_fit_donation_sanitizer_end_to_end(monkeypatch):
    """The fused step's donated dispatch really consumes its buffers —
    across an epoch boundary: the epoch-end get_params() host sync used
    to rebind the host param dict onto zero-copy borrows of the device
    buffers, pinning them against donation (NDArray.__setitem__ now
    copies host sources). One epoch would not catch that."""
    monkeypatch.setenv("MXNET_TPU_SANITIZE", "donation")
    _fused_fit(monkeypatch, num_epoch=3)


def test_trace_report_has_sanitizer_column():
    import importlib
    import sys

    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        trace_report = importlib.import_module("trace_report")
        importlib.reload(trace_report)
        assert "sanitizer_trips" in trace_report.DELTA_COLS
        out = trace_report.render([
            {"step": 1, "latency_ms": 5.0,
             "deltas": {"sanitizer_trips": 2}}])
        assert "san_trips" in out
    finally:
        sys.path.remove(os.path.join(ROOT, "tools"))
