"""Evidence hygiene for MFU experiment recording (round-6 satellite):
physically impossible measurements (mfu > 100%, step time below the
analytic FLOP floor) must be refused at record time and retro-tagged in
existing artifacts — a broken synchronization fence must never read as
a performance result."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(REPO, "tools"))

from mfu_experiments import (RESNET50_TRAIN_GFLOPS_PER_IMG, retag,
                             validate)


def _row(**over):
    row = {"experiment": "baseline", "imgs_per_sec": 1000.0,
           "step_time_ms": 256.0, "batch": 256, "image": 224,
           "compute_dtype": "bfloat16", "chip": "TPU v5 lite",
           "xla_flags": "", "mfu_pct": 50.0}
    row.update(over)
    return row


def test_validate_accepts_plausible_row():
    assert validate(_row()) is None


def test_validate_rejects_impossible_mfu():
    reason = validate(_row(mfu_pct=1095.3))
    assert reason and "mfu_pct" in reason


def test_validate_rejects_step_below_analytic_floor():
    # batch 256 at ~394 peak TFLOPS: floor ~= 256*12.267/394 ~= 8 ms;
    # 1.46 ms (the real 2026-07-31 garbage) is impossible even without
    # an mfu_pct field on the row
    reason = validate(_row(step_time_ms=1.46, mfu_pct=None))
    assert reason and "floor" in reason


def test_validate_skips_floor_for_unknown_chip():
    # no peak known -> the floor cannot be computed; only the mfu bound
    # applies
    assert validate(_row(chip="mystery accelerator",
                         step_time_ms=0.01, mfu_pct=None)) is None


def test_validate_skips_floor_for_small_images():
    # the analytic constant is the 224x224 ResNet-50 cost; CPU smoke
    # runs at 32x32 are not comparable
    assert validate(_row(image=32, step_time_ms=0.01,
                         mfu_pct=None)) is None


def test_retag_tags_only_invalid_untagged_rows(tmp_path):
    path = tmp_path / "mfu.jsonl"
    rows = [
        _row(),                                     # plausible: untouched
        _row(mfu_pct=411.5),                        # garbage: tag
        dict(_row(mfu_pct=999.0), valid=False,
             invalid_reason="already tagged"),      # tagged: untouched
        _row(step_time_ms=1.46, mfu_pct=None),      # floor garbage: tag
    ]
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    assert retag(str(path)) == 2
    out = [json.loads(l) for l in open(path)]
    assert "valid" not in out[0]
    assert out[1]["valid"] is False and "mfu_pct" in out[1]["invalid_reason"]
    assert out[2]["invalid_reason"] == "already tagged"
    assert out[3]["valid"] is False and "floor" in out[3]["invalid_reason"]
    # idempotent
    assert retag(str(path)) == 0


def test_repo_artifact_has_no_untagged_impossible_rows():
    """The acceptance bar itself: MFU_EXPERIMENTS.jsonl contains no
    untagged mfu_pct > 100 rows."""
    path = os.path.join(REPO, "MFU_EXPERIMENTS.jsonl")
    if not os.path.exists(path):
        pytest.skip("no MFU_EXPERIMENTS.jsonl")
    for line in open(path):
        if not line.strip():
            continue
        row = json.loads(line)
        if row.get("mfu_pct", 0) and row["mfu_pct"] > 100:
            assert row.get("valid") is False, \
                "untagged impossible row: %s" % line


def test_main_refuses_to_print_invalid_rows(monkeypatch, capsys):
    """stdout is the .jsonl destination (callers append it to
    MFU_EXPERIMENTS.jsonl): an invalid measurement must go to stderr
    only."""
    import mfu_experiments as mfu

    def fake_measure(variant, batch, image, num_classes, steps, dtype):
        r = _row(experiment=variant, mfu_pct=500.0)
        r["valid"] = False
        r["invalid_reason"] = "mfu_pct 500.0 exceeds 100% of chip peak"
        return r

    monkeypatch.setattr(mfu, "measure", fake_measure)
    mfu.main(["--variant", "baseline"])
    cap = capsys.readouterr()
    assert cap.out.strip() == ""
    assert "REFUSING" in cap.err
