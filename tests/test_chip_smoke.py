"""chip_smoke.py on the CPU: its train, serve and kernel phases at toy
width on ``mx.cpu(0)`` (the same functions, the same assertions — only the
sizes and the device differ), and the contract of the script itself:
without a TPU, or alone in a directory, it fails and prints no result."""
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

CHW = (3, 8, 8)
LR = 0.1     # toy width learns slower per step than ResNet-50 at 0.004


def _toy_net():
    return models.get_resnet([1, 1], [8, 8, 16], num_classes=16,
                             small_input=True)


@pytest.fixture(scope="module")
def trained():
    """Both train paths, once for the module: (classic, fused), each a
    ``(module, losses, record)`` from ``train_phase``."""
    mp = pytest.MonkeyPatch()
    mp.setenv("MXNET_COMPUTE_DTYPE", "bfloat16")
    try:
        ctx = [mx.cpu(0)]
        yield (chip_smoke.train_phase(_toy_net(), ctx, CHW, batch=16,
                                      steps=4, fused=False, lr=LR),
               # 32 steps: the serve phase wants a decisive model, and at
               # 16 or 24 the smallest top-2 margin hangs on bf16 rounding
               # order (0.06-0.21 there, 0.93-0.97 here)
               chip_smoke.train_phase(_toy_net(), ctx, CHW, batch=16,
                                      steps=32, fused=True, lr=LR))
    finally:
        mp.undo()


def test_train_phases_at_toy_width(trained):
    (_, classic, rec_c), (_, fused, rec_f) = trained
    # same seed, same data: the two paths start from the same loss
    assert abs(classic[0] - fused[0]) < 1e-2 * abs(classic[0])
    assert rec_c["path"] == "classic" and rec_c["dispatches_per_step"] > 1
    assert rec_f["path"] == "fused" and rec_f["dispatches_per_step"] == 1.0
    assert "MXNET_TPU_FUSED_STEP" not in os.environ   # restored


def test_serve_phase_at_toy_width(trained, capsys):
    mod = trained[1][0]
    rec = chip_smoke.serve_phase(mod, [mx.cpu(0)], CHW, requests=4,
                                 max_batch=4)
    assert rec["requests"] == 8
    assert rec["compiles"] <= len(rec["buckets"])
    assert rec["min_top2_margin"] > 0.1
    assert "chip_smoke serve " in capsys.readouterr().out


def test_placement_check_catches_a_buffer_on_the_wrong_device(trained):
    mod = trained[0][0]
    chip_smoke.assert_placement(mod, [mx.cpu(0)])
    with pytest.raises(AssertionError, match="lives on"):
        chip_smoke.assert_placement(mod, [mx.cpu(1)])


def test_train_phase_fails_when_the_loss_does_not_fall():
    # lr 0: the assertions are live, not decoration
    with pytest.raises(AssertionError, match="did not fall"):
        chip_smoke.train_phase(_toy_net(), [mx.cpu(0)], CHW, batch=16,
                               steps=2, fused=True, lr=0.0)


@pytest.mark.parametrize("name", chip_smoke.KERNEL_CASES)
def test_kernel_phase_through_the_interpreter(name):
    """A case a kernel, at the least shapes its rule admits: the kernel
    (interpreted here) against the ``jax.numpy`` body, forward and every
    gradient."""
    results = chip_smoke.kernel_phase(jax.devices()[0], small=True,
                                      only=(name,))
    assert set(results) == {name}


def test_kernel_phase_names_the_case_that_fails(monkeypatch):
    from mxnet_tpu.ops import seq

    real = seq.ssd_scan
    monkeypatch.setattr(seq, "ssd_scan", lambda *a: real(*a) * (
        1.1 if a[-1] else 1.0))
    with pytest.raises(AssertionError, match="ssd_scan: AssertionError"):
        chip_smoke.kernel_phase(jax.devices()[0], small=True,
                                only=("rtc axpy", "ssd_scan"))


@pytest.mark.multichip
def test_mesh_phase_on_four_cpu_devices(monkeypatch):
    """The --devices 4 checks (batch shards, param placement, the
    all-reduce, ONE compile of the step, the loss stream vs one device)
    in float32, where the streams agree closely; the chip run repeats
    them in bf16 at full width."""
    monkeypatch.delenv("MXNET_COMPUTE_DTYPE", raising=False)
    _, ref, _ = chip_smoke.train_phase(_toy_net(), [mx.cpu(0)], CHW,
                                       batch=16, steps=3, fused=True, lr=LR)
    ctx = [mx.cpu(i) for i in range(4)]
    for fsdp, mesh in ((1, {"dp": 4}), (4, {"dp": 1, "fsdp": 4})):
        rec = chip_smoke.mesh_phase(_toy_net(), ctx, CHW, batch=16, steps=3,
                                    fsdp=fsdp, ref_losses=ref, lr=LR)
        assert rec["mesh"] == mesh
        assert (rec["sharded_params"] > 0) == (fsdp > 1)
        assert "all-reduce" in rec["collectives"]
        # float32: the whole stream is tight, not just the first steps
        np.testing.assert_allclose(rec["losses"], ref, rtol=1e-3)


def _clean_env(**over):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(over)
    return env


def test_without_a_tpu_the_script_fails_naming_it():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       cwd=REPO, env=_clean_env(JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
    assert r.stdout.startswith("chip_smoke: platform=cpu ")


def test_alone_in_a_directory_the_script_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"],
                       capture_output=True, text=True, timeout=120,
                       cwd=str(tmp_path), env=_clean_env(JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "mxnet_tpu" in r.stderr
    assert r.stdout == ""
