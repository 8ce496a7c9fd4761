"""graftrace concurrency analysis + lock/deadlock sanitizers:
good/bad fixture pairs per rule family, suppression, registration into
the graftlint driver, the whole-tree tier-1 gate for the concurrency
families, and seeded runtime violations (an ABBA lock inversion caught
by the `locks` sanitizer; a stalled progress signal tripping the
deadlock watchdog into a FlightRecorder dump with all-thread stacks)."""
import json
import os
import threading
import time

import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.analysis import graftlint, graftrace, sanitizers
from mxnet_tpu.analysis.sanitizers import (DeadlockWatchdog,
                                           InstrumentedLock,
                                           LockOrderRegistry,
                                           SanitizerError)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONC_RULES = frozenset(graftrace.RULES)


def _rules(findings):
    return sorted({f.rule for f in findings})


def _lint(src, path="pkg/worker.py", rules=CONC_RULES):
    cfg = graftlint.Config(declared_env={"MXNET_TPU_DECLARED"},
                           rules=rules)
    return graftlint.analyze_source(src, path, cfg)


# ---------------------------------------------------------------------------
# registration into the graftlint driver
# ---------------------------------------------------------------------------

def test_concurrency_rules_registered_as_default():
    assert set(graftrace.RULES) <= set(graftlint.RULES)
    assert set(graftrace.RULES) <= graftlint.Config().rules
    for rule, tag in graftrace.SUPPRESS_TAGS.items():
        assert graftlint.SUPPRESS_TAGS[rule] == tag


# ---------------------------------------------------------------------------
# lock-order rule
# ---------------------------------------------------------------------------

BAD_ABBA = """
import threading

class W:
    def __init__(self):
        self.a_lock = threading.Lock()
        self.b_lock = threading.Lock()

    def f(self):
        with self.a_lock:
            with self.b_lock:
                pass

    def g(self):
        with self.b_lock:
            with self.a_lock:
                pass
"""


def test_lock_order_flags_abba_cycle():
    bad = _lint(BAD_ABBA)
    assert _rules(bad) == ["lock-order"]
    # both directions of the cycle are reported
    assert len(bad) == 2
    assert "deadlock" in bad[0].message


def test_lock_order_consistent_nesting_is_clean():
    src = BAD_ABBA.replace(
        "with self.b_lock:\n            with self.a_lock:",
        "with self.a_lock:\n            with self.b_lock:")
    assert _lint(src) == []


def test_lock_order_cycle_through_method_call():
    # g holds B and calls h, which takes A; f takes A then B -> cycle
    src = """
import threading

class W:
    def __init__(self):
        self.a_lock = threading.Lock()
        self.b_lock = threading.Lock()

    def f(self):
        with self.a_lock:
            with self.b_lock:
                pass

    def h(self):
        with self.a_lock:
            pass

    def g(self):
        with self.b_lock:
            self.h()
"""
    assert "lock-order" in _rules(_lint(src))


def test_lock_order_suppression():
    src = BAD_ABBA.replace(
        "with self.b_lock:\n            with self.a_lock:",
        "with self.b_lock:  # graft: lock-order-ok\n"
        "            with self.a_lock:  # graft: lock-order-ok")
    # suppressing one direction still leaves the other edge's findings
    remaining = _lint(src)
    assert all(f.line < 14 for f in remaining)


# ---------------------------------------------------------------------------
# blocking-under-lock rule
# ---------------------------------------------------------------------------

def test_blocking_under_lock_flags_queue_get():
    src = """
class W:
    def take(self):
        with self._lock:
            return self._queue.get()
"""
    bad = _lint(src)
    assert _rules(bad) == ["blocking-under-lock"]
    assert "no timeout" in bad[0].message


def test_blocking_under_lock_timeout_or_unlocked_is_clean():
    src = """
class W:
    def take(self):
        with self._lock:
            return self._queue.get(timeout=0.5)

    def take2(self):
        return self._queue.get()
"""
    assert _lint(src) == []


def test_blocking_under_lock_flags_join_sleep_socket_jax():
    for call in ("t.join()", "time.sleep(1)", "sock.recv(1024)",
                 "x.block_until_ready()", "jnp.dot(a, b)"):
        src = ("class W:\n"
               "    def f(self):\n"
               "        with self._lock:\n"
               "            %s\n" % call)
        assert _rules(_lint(src)) == ["blocking-under-lock"], call


def test_blocking_under_lock_interprocedural():
    src = """
def slow():
    return sock.recv(4)

class W:
    def f(self):
        with self._lock:
            return slow()
"""
    bad = _lint(src)
    assert _rules(bad) == ["blocking-under-lock"]
    assert "slow" in bad[0].message


def test_cv_wait_needs_predicate_loop_or_timeout():
    bad = """
class W:
    def f(self):
        with self._cv:
            self._cv.wait()
"""
    assert _rules(_lint(bad)) == ["blocking-under-lock"]
    good_loop = """
class W:
    def f(self):
        with self._cv:
            while not self._ready:
                self._cv.wait()
"""
    assert _lint(good_loop) == []
    good_timeout = bad.replace("wait()", "wait(timeout=1.0)")
    assert _lint(good_timeout) == []


def test_blocking_under_lock_suppression():
    src = """
class W:
    def f(self):
        with self._lock:
            t.join()  # graft: blocking-ok
"""
    assert _lint(src) == []


# ---------------------------------------------------------------------------
# thread-lifecycle rule
# ---------------------------------------------------------------------------

def test_lifecycle_flags_nondaemon_thread_without_join():
    src = """
import threading

class W:
    def start(self):
        self._t = threading.Thread(target=self._run)
        self._t.start()
"""
    bad = _lint(src)
    assert _rules(bad) == ["thread-lifecycle"]
    assert "non-daemon" in bad[0].message


def test_lifecycle_daemon_or_joined_thread_is_clean():
    daemon = """
import threading

class W:
    def start(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()
"""
    assert _lint(daemon) == []
    joined = """
import threading

class W:
    def start(self):
        self._t = threading.Thread(target=self._run)
        self._t.start()

    def stop(self):
        self._t.join(timeout=5.0)
"""
    assert _lint(joined) == []


def test_lifecycle_flags_unbounded_join_on_shutdown_path():
    src = """
class W:
    def close(self):
        self._t.join()
"""
    bad = _lint(src)
    assert _rules(bad) == ["thread-lifecycle"]
    assert "shutdown path" in bad[0].message
    assert _lint(src.replace("join()", "join(timeout=5.0)")) == []


def test_lifecycle_flags_start_in_init_without_teardown():
    src = """
import threading

class W:
    def __init__(self):
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()
"""
    bad = _lint(src)
    assert _rules(bad) == ["thread-lifecycle"]
    assert "no reachable" in bad[0].message
    with_close = src + """
    def close(self):
        self._t.join(timeout=1.0)
"""
    assert _lint(with_close) == []


def test_lifecycle_flags_stop_event_set_after_join():
    src = """
import threading

class W:
    def __init__(self):
        self._stop_event = threading.Event()

    def close(self):
        self._t.join(timeout=1.0)
        self._stop_event.set()
"""
    bad = _lint(src)
    assert any("after the join" in f.message for f in bad)
    ordered = """
import threading

class W:
    def __init__(self):
        self._stop_event = threading.Event()

    def close(self):
        self._stop_event.set()
        self._t.join(timeout=1.0)
"""
    assert _lint(ordered) == []


# ---------------------------------------------------------------------------
# fork-safety rule
# ---------------------------------------------------------------------------

def test_fork_safety_flags_bound_method_target_and_self_args():
    src = """
import multiprocessing

class W:
    def spawn(self):
        p = multiprocessing.Process(target=self._run)
        p.start()
        p.join(timeout=5.0)
"""
    bad = _lint(src)
    assert _rules(bad) == ["fork-safety"]
    assert "bound method" in bad[0].message
    src2 = """
import multiprocessing

def main(w):
    p = multiprocessing.Process(target=work, args=(w.engine_lock,))
    p.start()
    p.join(timeout=5.0)
"""
    assert _rules(_lint(src2)) == ["fork-safety"]


def test_fork_safety_module_level_target_is_clean():
    src = """
import multiprocessing

def work(q):
    pass

class W:
    def spawn(self):
        p = multiprocessing.Process(target=work, args=(self.depth,))
        p.start()
        p.join(timeout=5.0)
"""
    assert _lint(src) == []


def test_fork_safety_flags_fork_start_method():
    src = "import multiprocessing\n" \
          "ctx = multiprocessing.get_context('fork')\n"
    bad = _lint(src)
    assert _rules(bad) == ["fork-safety"]
    assert _lint(src.replace("'fork'", "'spawn'")) == []


# ---------------------------------------------------------------------------
# whole-tree gate (tier-1): concurrency families, empty baseline
# ---------------------------------------------------------------------------

def test_repo_tree_clean_under_concurrency_rules():
    cfg = graftlint.Config(rules=CONC_RULES)
    findings = graftlint.analyze_paths(
        [os.path.join(ROOT, "mxnet_tpu"), os.path.join(ROOT, "tools")],
        cfg, root=ROOT)
    assert findings == [], \
        "new concurrency findings (fix or annotate):\n%s" % "\n".join(
            repr(f) for f in findings)


# ---------------------------------------------------------------------------
# runtime: lock-order sanitizer
# ---------------------------------------------------------------------------

def test_instrumented_lock_raises_on_abba_inversion():
    """Seeded inversion: thread 1 exhibits A->B; the main thread then
    attempts B->A and gets a SanitizerError instead of a deadlock."""
    reg = LockOrderRegistry()
    a = InstrumentedLock(threading.Lock(), "A", registry=reg)
    b = InstrumentedLock(threading.Lock(), "B", registry=reg)

    def ab():
        with a:
            with b:
                pass

    t = threading.Thread(target=ab)
    t.start()
    t.join(timeout=10)
    telemetry.reset()
    telemetry.enable()
    try:
        with b:
            with pytest.raises(SanitizerError, match="lock-order"):
                with a:
                    pass
        assert telemetry.peek("sanitizer.trips.locks") == 1
    finally:
        telemetry.disable()
        telemetry.reset()


def test_instrumented_lock_consistent_order_and_reentry_ok():
    reg = LockOrderRegistry()
    a = InstrumentedLock(threading.RLock(), "A", registry=reg)
    b = InstrumentedLock(threading.Lock(), "B", registry=reg)
    for _ in range(2):
        with a:
            with a:      # re-entrant acquire records no self-edge
                with b:
                    pass
    # same order again from another thread: still fine
    t = threading.Thread(target=lambda: a.acquire() and None)
    with a:
        with b:
            pass


def test_instrumented_condition_keeps_cv_semantics():
    reg = LockOrderRegistry()
    cv = InstrumentedLock(threading.Condition(), "CV", registry=reg)
    hits = []

    def consumer():
        with cv:
            while not hits:
                cv.wait(timeout=5.0)
            hits.append("consumed")

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.05)
    with cv:
        hits.append("produced")
        cv.notify_all()
    t.join(timeout=10)
    assert not t.is_alive()
    assert hits == ["produced", "consumed"]


def test_lock_wait_telemetry_histogram():
    telemetry.reset()
    telemetry.enable()
    try:
        reg = LockOrderRegistry()
        lk = InstrumentedLock(threading.Lock(), "tst", registry=reg)
        with lk:
            pass
        assert telemetry.histogram("lock.wait_ms").count == 1
        assert telemetry.histogram("lock.wait_ms.tst").count == 1
    finally:
        telemetry.disable()
        telemetry.reset()


def test_maybe_instrument_gated_by_env(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_SANITIZE", "")
    raw = threading.Lock()
    assert sanitizers.maybe_instrument(raw, "x") is raw
    monkeypatch.setenv("MXNET_TPU_SANITIZE", "locks")
    wrapped = sanitizers.maybe_instrument(raw, "x")
    assert isinstance(wrapped, InstrumentedLock)


def test_engine_locks_instrumented_when_armed(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_SANITIZE", "locks")
    from mxnet_tpu.engine import ThreadedEngine

    eng = ThreadedEngine(num_workers=2)
    try:
        assert isinstance(eng._heap_lock, InstrumentedLock)
        done = []
        eng.push(lambda: done.append(1))
        eng.wait_for_all()
        assert done == [1]
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# runtime: deadlock watchdog
# ---------------------------------------------------------------------------

def test_watchdog_dumps_stacks_on_stall(tmp_path, monkeypatch):
    """Seeded stall: a progress fn that never advances trips the
    watchdog, which counts the trip and writes a FlightRecorder dump
    whose stacks.txt contains every live thread's stack."""
    from mxnet_tpu import tracing

    monkeypatch.setenv("MXNET_TPU_CRASH_DIR", str(tmp_path))
    telemetry.reset()
    telemetry.enable()
    parked = threading.Event()
    release = threading.Event()

    def parked_thread():
        parked.set()
        release.wait(timeout=30)

    t = threading.Thread(target=parked_thread,
                         name="test-parked-worker", daemon=True)
    t.start()
    parked.wait(timeout=10)
    wd = DeadlockWatchdog(progress_fn=lambda: 0,
                          threshold_s=0.2, interval_s=0.05)
    wd.start()
    try:
        deadline = time.time() + 20
        while wd.trips == 0 and time.time() < deadline:
            time.sleep(0.05)
    finally:
        release.set()
        wd.stop()
        t.join(timeout=10)
    assert wd.trips == 1
    assert telemetry.peek("sanitizer.trips.deadlock") == 1
    assert wd.last_dump is not None
    stacks = open(os.path.join(wd.last_dump, "stacks.txt")).read()
    assert "test-parked-worker" in stacks
    assert "release.wait" in stacks
    with open(os.path.join(wd.last_dump, "meta.json")) as f:
        assert "deadlock-watchdog" in json.load(f)["reason"]
    telemetry.disable()
    telemetry.reset()


def test_watchdog_quiet_while_progressing():
    ticks = []

    def progress():
        ticks.append(1)
        return len(ticks)     # always advancing

    wd = DeadlockWatchdog(progress_fn=progress,
                          threshold_s=0.2, interval_s=0.02)
    wd.start()
    time.sleep(0.6)
    wd.stop()
    assert wd.trips == 0


def test_tracing_starts_and_stops_watchdog(monkeypatch):
    from mxnet_tpu import tracing

    monkeypatch.setenv("MXNET_TPU_SANITIZE", "deadlock")
    monkeypatch.setenv("MXNET_TPU_WATCHDOG_S", "3600")
    telemetry.enable()
    try:
        tracing.maybe_init()
        assert tracing._watchdog is not None
        names = {t.name for t in threading.enumerate()}
        assert "mxtpu-watchdog" in names
    finally:
        tracing.shutdown()
        telemetry.disable()
        telemetry.reset()
    assert tracing._watchdog is None
    assert "mxtpu-watchdog" not in {t.name for t in threading.enumerate()}


# ---------------------------------------------------------------------------
# satellites: trace_report lock view, MetricsServer.stop
# ---------------------------------------------------------------------------

def test_trace_report_lock_contention_view(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    snap = {
        "lock": {"wait_ms": {
            "_value": {"count": 7, "sum": 3.5, "mean": 0.5, "min": 0.1,
                       "max": 1.2, "p50": 0.4, "p90": 1.0, "p99": 1.2},
            "engine-heap": {"count": 5, "sum": 2.5, "mean": 0.5,
                            "min": 0.1, "max": 1.2, "p50": 0.4,
                            "p90": 1.0, "p99": 1.2},
        }},
        "sanitizer": {"trips": {"_value": 2, "locks": 1, "deadlock": 1}},
    }
    out = trace_report.render_locks(snap)
    assert "lock contention" in out
    assert "engine-heap" in out
    assert "(all)" in out
    assert "sanitizer trips: 2" in out
    assert "deadlock=1" in out
    # and the crash-dump report path picks it up end to end
    d = tmp_path / "flight-test-pid1-1"
    d.mkdir()
    (d / "telemetry.json").write_text(json.dumps(snap))
    report = trace_report.report_crash_dump(str(d))
    assert "lock contention" in report
    # a snapshot with no lock/sanitizer data renders nothing
    assert trace_report.render_locks({}) == ""


def test_metrics_server_stop_joins_thread():
    from mxnet_tpu import tracing

    srv = tracing.MetricsServer(0)
    assert any(t.name == "mxtpu-metrics" for t in threading.enumerate())
    srv.stop()
    assert not any(t.name == "mxtpu-metrics"
                   for t in threading.enumerate())
    srv.stop()     # idempotent; close is an alias
    srv.close()
