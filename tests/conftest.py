"""Test configuration: force an 8-device CPU platform so multi-device
sharding paths run without TPU hardware (the reference's analogue: CPU-only
multi-device tests like tests/python/unittest/test_multi_device_exec.py)."""
import multiprocessing
import os
import time

# force CPU: on a TPU host the chip is the default backend, but tests run
# on the virtual 8-device CPU mesh and must never take the chip
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# full-precision matmuls/convs so finite-difference gradient checks are tight
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")

# parameter-server frame auth is default-on (the server refuses to start
# without a secret); the suite runs authenticated end to end, like every
# launch.py job. Worker subprocesses inherit this env.
os.environ.setdefault("MXTPU_PS_SECRET", "test-suite-token")

import jax  # noqa: E402

# explicit, in case jax was imported (and read the env) before this file
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# modules exercising the fused one-dispatch step run with the transfer
# sanitizer armed: jax.transfer_guard("disallow") around every fit's
# step loop, so an implicit host<->device transfer regression in the
# fused path fails these suites at the batch that caused it (see
# docs/static_analysis.md)
_TRANSFER_SANITIZED = {"test_fused_step", "test_fused_feed",
                       "test_sharded_fused", "test_checkpoint",
                       "test_numwatch", "test_fsdp"}


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multichip: needs the forced 8-device cpu mesh (skipped when the "
        "backend refused --xla_force_host_platform_device_count)")


def pytest_collection_modifyitems(config, items):
    if len(jax.devices()) >= 8:
        return
    skip = pytest.mark.skip(
        reason="backend refused the forced 8-device cpu platform")
    for item in items:
        if "multichip" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _arm_transfer_sanitizer(request, monkeypatch):
    if request.module.__name__.rpartition(".")[2] in _TRANSFER_SANITIZED \
            and "MXNET_TPU_SANITIZE" not in os.environ:
        monkeypatch.setenv("MXNET_TPU_SANITIZE", "transfer")
    yield


@pytest.fixture(autouse=True)
def _xprof_override_ends_with_its_test():
    """``xprof.enable()`` / ``disable()`` override telemetry's switch for
    the PROCESS. A test that leaves ``disable()`` behind turns the compile
    registry off for every later file on its worker (which files share a
    worker is timing: ``tests/test_benchmark_rehearsals.py``'s traced run
    then printed no ``step_program_*`` metric, once in a whole run). The
    override a test set is taken back when it ends."""
    from mxnet_tpu import xprof

    before = xprof._override
    yield
    xprof._override = before


@pytest.fixture(autouse=True)
def _no_thread_or_process_leaks(request):
    """Every test must clean up after itself on the concurrency plane:
    no new non-daemon threads and no live child processes may survive a
    test (graftrace's runtime counterpart — a leaked thread here is
    exactly the lifecycle hazard the static rules flag). Daemon threads
    (engine/feed workers live process-long by design) are exempt; brief
    stragglers get a join grace before we call them a leak."""
    import threading

    before = {t.ident for t in threading.enumerate()}
    yield
    deadline = time.time() + 5.0
    while time.time() < deadline:
        leaked = [t for t in threading.enumerate()
                  if t.ident not in before and t.is_alive()
                  and not t.daemon]
        if not leaked:
            break
        for t in leaked:
            t.join(timeout=0.2)
    else:
        pytest.fail("test leaked non-daemon thread(s): %s"
                    % ", ".join(t.name for t in leaked))
    procs = [p for p in multiprocessing.active_children() if p.is_alive()]
    for p in procs:
        p.join(timeout=5.0)
    procs = [p for p in procs if p.is_alive()]
    assert not procs, ("test leaked child process(es): %s"
                       % ", ".join("%s(pid=%s)" % (p.name, p.pid)
                                   for p in procs))
    # profiler sessions are process-global singletons in jax: one left
    # open poisons every later capture attempt with "already active"
    import sys as _sys

    prof = _sys.modules.get("mxnet_tpu.profiler")
    if prof is not None and prof.is_running():
        try:
            prof.stop()
        except Exception:
            pass
        pytest.fail("test left a profiler trace session open "
                    "(call profiler.stop() or use the context manager)")
