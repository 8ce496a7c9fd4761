"""CPU rehearsals of a run at toy width: the last line's keys, the look
for the chip, and a timed path broken underneath (``correct`` must come
out false)."""
import json
import os
import subprocess
import sys
import time

import pytest

import toy
from benchmark import harness
from benchmark.drivers import fit

ROOT = toy.ROOT


@pytest.fixture
def on_cpu(monkeypatch):
    """The test, not the harness, decides to go on without a chip."""
    import jax

    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "flops_per_s": {"float32": 1e12, "bfloat16": 2e12}, "hbm_bytes_per_s": 1e11})
    for key in ("MXNET_COMPUTE_DTYPE", "MXNET_TPU_FUSED_STEP"):
        monkeypatch.setenv(key, "")   # so that what the driver sets is
        monkeypatch.delenv(key)       # taken back after the test


def _run(cell, capsys, seed=3000000019, seconds=1.0):
    # as if the process had taken 1000 s to get its devices
    rows = fit.run(cell, seed=seed, seconds=seconds, trace=False,
                   t_start=time.perf_counter() - 1000.0)["rows"]
    out = capsys.readouterr().out.strip().splitlines()
    return rows, json.loads(out[-1]), out


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "inception_bn_fit_resident", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout


CHECKS = ["loss_step1_rel_gap", "loss_step2_rel_gap", "loss_step3_rel_gap",
          "step1_excess_noise", "grad_norm_gap", "grad_norm_median_leaf_gap",
          "delta_norm_median_leaf_gap", "dead_leaves"]


@pytest.mark.parametrize("fused", [True, False])
def test_last_line_and_checks(on_cpu, capsys, fused):
    cell = toy.cell("resnet", fused=fused, compute_dtype="float32")
    rows, line, out = _run(cell, capsys)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    # set-up is timed from the devices on; the way there is only printed
    assert line["metrics"]["setup_s"]["value"] < 1000.0
    assert any(l.startswith("process +1000.") for l in out)
    # every number compared is printed beside its limit, and in float32
    # the program sits on the reference: no gap, and none of the noise
    # the stated precision's own pipeline carries (-1)
    got = {r[0]: r[1] for r in rows}
    assert [r[0] for r in rows[-len(CHECKS):]] == CHECKS
    assert got.pop("step1_excess_noise") == pytest.approx(-1.0, abs=1e-3)
    assert all(got[name] < 2e-3 for name in CHECKS if name in got), rows
    assert sum(l.startswith("check ") for l in out) == len(rows)
    assert any(l.startswith("chunk rates") for l in out)


def test_a_stray_program_switch_in_the_environment_is_refused(
        on_cpu, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    with pytest.raises(SystemExit, match="MXNET_TPU_FUSED_STEP"):
        fit.run(toy.cell("resnet"), seed=1, seconds=1.0, trace=False,
                t_start=time.perf_counter())


def _failed(rows):
    return {name for name, value, limit, _ in rows if value > limit}


def test_a_step_that_leaves_the_state_unchanged_is_not_correct(
        on_cpu, capsys, monkeypatch):
    """The classic loop's update switched off underneath the harness: no
    leaf has moved after three steps, and ``correct`` is false."""
    import mxnet_tpu as mx

    monkeypatch.setattr(mx.mod.Module, "update", lambda self: None)
    rows, line, _ = _run(toy.cell("resnet", compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert {"delta_norm_median_leaf_gap", "dead_leaves"} <= _failed(rows)


@pytest.mark.parametrize("fault,fails,passes", [
    # half the learning rate: every leaf's first gradient, as the
    # optimizer's state shows it, reads half the reference's
    ({"learning_rate": 0.002}, "grad_norm_median_leaf_gap", None),
    # half the momentum: the first step is the reference's, the three
    # together fall short (1.75 + 1.5 + 1 of the three gradients for
    # 2.71 + 1.9 + 1)
    ({"momentum": 0.45}, "delta_norm_median_leaf_gap",
     "grad_norm_median_leaf_gap"),
])
def test_a_wrong_update_is_not_correct(on_cpu, capsys, monkeypatch, fault,
                                       fails, passes):
    """The optimizer handed another recipe than the configuration's
    underneath the harness (the reference follows the configuration's):
    an update that runs, but wrong."""
    import mxnet_tpu as mx

    fit_ = mx.mod.Module.fit

    def wrong(self, *args, **kw):
        kw["optimizer_params"] = dict(kw["optimizer_params"], **fault)
        return fit_(self, *args, **kw)

    monkeypatch.setattr(mx.mod.Module, "fit", wrong)
    rows, line, _ = _run(toy.cell("resnet", fused=True,
                                  compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert fails in _failed(rows) and passes not in _failed(rows)
    assert "dead_leaves" not in _failed(rows)


def test_traced_run_reports_every_per_layer_metric(on_cpu, capsys,
                                                   monkeypatch):
    """``--trace 1`` on the CPU: the profiler runs, the harness's spans
    come back out of the trace, and every per-layer metric the cell lists
    is on the line. The CPU has no TPU plane, so the test lends the
    reduction one device event; the numbers mean nothing here."""
    from benchmark.trace import reduce as R

    real = R.reduce

    def with_a_device_plane(trace, steps):
        start = R.host_spans(trace)[0][1]
        trace["planes"].append({"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops",
             "events": [["fusion.1", "fusion:kOutput", start, 1e6]]}]})
        return real(trace, steps)

    monkeypatch.setattr(R, "reduce", with_a_device_plane)
    cell = toy.cell("resnet", fused=True, compute_dtype="float32")
    fit.run(cell, seed=7, seconds=3.0, trace=True,
            t_start=time.perf_counter())
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    want = set(harness.metric_names(cell["spec"], "per_layer",
                                    "resnet50_fit_resident"))
    assert len(want) >= 10 and set(line["metrics"]) == want
    assert line["metrics"]["fit_dispatches_per_step"]["value"] == 1.0
    assert line["metrics"]["step_compiles_in_window"]["value"] == 0.0
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["breakdown"]["idle_gaps"][0][0] == "fit_loop"
