"""The closed-loop autotuner: search determinism and pruning off a
fake compile registry, the validate() fence on JSONL writes, the
best-config cache with its lookup fallback order, the trace_report tune
view, and the one-dispatch regression pin for tuned kernels inside the
fused step."""
import json
import os
import sys

import numpy as np
import pytest

from mxnet_tpu import autotune
from mxnet_tpu.base import MXNetError

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(REPO, "tools"))


# ---------------------------------------------------------------------------
# search core off a fake registry
# ---------------------------------------------------------------------------

def _fake_site():
    """Three candidates with known registry facts and known run times:
    default (2 ms), a winner (1 ms), and an OOM candidate."""
    cands = [
        {"name": "default", "config": {"tile": 128}},
        {"name": "fast", "config": {"tile": 256}},
        {"name": "huge", "config": {"tile": 1024}},
    ]
    facts = {
        "default": {"flops": 1e9, "peak_bytes": 100, "compile_time_s": 0.1},
        "fast": {"flops": 1e9, "peak_bytes": 200, "compile_time_s": 0.1},
        "huge": {"flops": 1e9, "peak_bytes": 10_000, "compile_time_s": 0.1},
    }
    times = {"default": 2e-3, "fast": 1e-3, "huge": 0.5e-3}
    return (cands,
            lambda c: dict(facts[c["name"]]),
            lambda c: times[c["name"]])


def test_search_picks_winner_and_prunes_preflight():
    cands, compile_fn, run_fn = _fake_site()
    result, rows = autotune.search("fake", cands, compile_fn, run_fn,
                                   limit_bytes=1000)
    assert result["best"]["candidate"] == "fast"
    assert result["non_default"] is True
    assert result["pruned_preflight"] == 1
    assert result["measured"] == 2
    assert result["speedup_vs_default"] == pytest.approx(2.0)
    huge = next(r for r in rows if r["candidate"] == "huge")
    assert "pre-flight OOM" in huge["pruned"]
    assert "step_time_ms" not in huge
    # the winner row is flagged on every row list
    assert [r.get("best") for r in rows
            if "step_time_ms" in r] == [False, True]


def test_search_is_deterministic():
    cands, compile_fn, run_fn = _fake_site()
    a = autotune.search("fake", cands, compile_fn, run_fn,
                        limit_bytes=1000)
    b = autotune.search("fake", cands, compile_fn, run_fn,
                        limit_bytes=1000)
    assert a == b


def test_search_roofline_prune():
    """A candidate whose FLOP floor at chip peak already exceeds the
    best measured time must be pruned without being run."""
    cands = [
        {"name": "default", "config": {}},
        {"name": "bloated", "config": {}},
    ]
    facts = {"default": {"flops": 1e9},
             # 1e12 FLOPs at 100 TFLOPS -> 10 ms floor > 2 ms best
             "bloated": {"flops": 1e12}}
    ran = []

    def run_fn(c):
        ran.append(c["name"])
        return 2e-3

    result, rows = autotune.search(
        "fake", cands, lambda c: dict(facts[c["name"]]), run_fn,
        peak_tflops=100.0)
    assert result["pruned_roofline"] == 1
    assert "bloated" not in ran
    bl = next(r for r in rows if r["candidate"] == "bloated")
    assert "roofline-hopeless" in bl["pruned"]


def test_search_budget_prune_with_fake_clock():
    cands, compile_fn, run_fn = _fake_site()
    t = [0.0]

    def clock():
        t[0] += 10.0
        return t[0]

    result, rows = autotune.search("fake", cands, compile_fn, run_fn,
                                   budget_s=5.0, clock=clock)
    # the default always runs; everything after blows the budget
    assert result["measured"] == 1
    assert result["pruned_budget"] == 2
    assert all("budget exhausted" in r["pruned"] for r in rows[1:])


def test_search_inapplicable_candidate():
    def compile_fn(c):
        if c["name"] == "bad":
            raise MXNetError("candidate 'bad' not applicable")
        return {"flops": 1.0}

    result, rows = autotune.search(
        "fake",
        [{"name": "default", "config": {}}, {"name": "bad", "config": {}}],
        compile_fn, lambda c: 1e-3)
    assert result["pruned_inapplicable"] == 1
    assert result["best"]["candidate"] == "default"


# ---------------------------------------------------------------------------
# the validate() fence on JSONL writes
# ---------------------------------------------------------------------------

def _row(**over):
    row = {"experiment": "baseline", "imgs_per_sec": 1000.0,
           "step_time_ms": 256.0, "batch": 256, "image": 224,
           "compute_dtype": "bfloat16", "chip": "TPU v5 lite",
           "xla_flags": "", "mfu_pct": 50.0}
    row.update(over)
    return row


def test_validate_accepts_plausible_row():
    assert autotune.validate(_row()) is None


def test_validate_rejects_impossible_mfu():
    reason = autotune.validate(_row(mfu_pct=1095.3))
    assert reason and "mfu_pct" in reason


def test_validate_rejects_step_below_analytic_floor():
    # batch 256 at 197 peak TFLOPS: floor = 256*12.267/197 ~= 16 ms;
    # 1.46 ms is impossible even without an mfu_pct field on the row
    reason = autotune.validate(_row(step_time_ms=1.46, mfu_pct=None))
    assert reason and "floor" in reason


def test_validate_skips_floor_for_unknown_chip():
    # no peak known -> the floor cannot be computed; only the mfu bound
    # applies
    assert autotune.validate(_row(chip="mystery accelerator",
                                  step_time_ms=0.01, mfu_pct=None)) is None


def test_validate_skips_floor_for_small_images():
    # the analytic constant is the 224x224 ResNet-50 cost; CPU smoke
    # runs at 32x32 are not comparable
    assert autotune.validate(_row(image=32, step_time_ms=0.01,
                                  mfu_pct=None)) is None


def test_record_refuses_physically_impossible_rows(tmp_path):
    path = str(tmp_path / "rows.jsonl")
    rows = [
        {"experiment": "autotune:fake:a", "site": "fake",
         "candidate": "a", "config": {}, "step_time_ms": 2.0},
        # mfu over 100% of chip peak: the fence must refuse it
        {"experiment": "autotune:fake:b", "site": "fake",
         "candidate": "b", "config": {}, "step_time_ms": 1.0,
         "mfu_pct": 1095.0},
    ]
    rec = autotune.record(rows, path)
    assert rec["written"] == 1 and rec["refused"] == 1
    assert "exceeds 100%" in rec["refused_rows"][0]["refused"]
    on_disk = [json.loads(l) for l in open(path)]
    assert len(on_disk) == 1
    assert all(r["valid"] is True for r in on_disk)


# ---------------------------------------------------------------------------
# best-config cache
# ---------------------------------------------------------------------------

def test_cache_roundtrip_and_lookup_fallback(tmp_path):
    path = str(tmp_path / "cache.json")
    autotune.save_best("conv_backward", {"kernel": "pallas"},
                       sig="(2,8,8,128)float32", chip="v5e", path=path)
    autotune.save_best("conv_backward", {"kernel": "xla"},
                       chip="*", path=path)
    # exact hit wins over wildcards
    assert autotune.best_config("conv_backward", "(2,8,8,128)float32",
                                "v5e", path=path) == {"kernel": "pallas"}
    # unknown sig/chip falls back to the site-wide entry
    assert autotune.best_config("conv_backward", "(9,9)f32", "v6e",
                                path=path) == {"kernel": "xla"}
    assert autotune.best_config("norm_act", path=path) is None
    # atomic write left valid JSON behind
    cache = json.load(open(path))
    assert set(cache["entries"]) == {
        "conv_backward|(2,8,8,128)float32|v5e", "conv_backward|*|*"}


def test_consumers_default_off(monkeypatch, tmp_path):
    """With the knobs off nothing consults the cache: defaults apply,
    zero behavior change."""
    monkeypatch.delenv("MXNET_TPU_AUTOTUNE", raising=False)
    monkeypatch.delenv("MXNET_TPU_PALLAS_CONV", raising=False)
    assert autotune.conv_kernel_enabled() is False
    assert autotune.norm_block_rows() is None


def test_conv_kernel_enabled_via_cache(monkeypatch, tmp_path):
    path = str(tmp_path / "cache.json")
    autotune.save_best("conv_backward", {"kernel": "pallas"},
                       chip=autotune._chip_kind(), path=path)
    monkeypatch.setattr(autotune, "CACHE_FILE", path)
    monkeypatch.setattr(autotune, "_cache_memo", None)
    monkeypatch.delenv("MXNET_TPU_PALLAS_CONV", raising=False)
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "1")
    assert autotune.conv_kernel_enabled() is True
    # the pin overrides even an empty cache
    monkeypatch.setattr(autotune, "_cache_memo", None)
    monkeypatch.setattr(autotune, "CACHE_FILE",
                        str(tmp_path / "missing.json"))
    monkeypatch.delenv("MXNET_TPU_AUTOTUNE", raising=False)
    assert autotune.conv_kernel_enabled() is False
    monkeypatch.setenv("MXNET_TPU_PALLAS_CONV", "1")
    assert autotune.conv_kernel_enabled() is True


# ---------------------------------------------------------------------------
# the smoke search end to end
# ---------------------------------------------------------------------------

def test_run_smoke_non_default_winner(tmp_path):
    """The acceptance criterion: on the cpu interpreter the autotuner
    must demonstrably pick a non-default winning config, write only
    valid rows, and persist the winners."""
    jsonl = str(tmp_path / "rows.jsonl")
    cache = str(tmp_path / "cache.json")
    s = autotune.run_smoke(budget=120.0, jsonl_path=jsonl,
                           cache_path=cache)
    assert s["non_default_winner"] is True
    assert s["rows_refused"] == 0
    na = s["sites"]["norm_act"]
    assert na["best"]["config"]["block_rows"] != 128
    assert na["speedup_vs_default"] > 1.0
    rows = [json.loads(l) for l in open(jsonl)]
    assert rows and all(r["valid"] is True for r in rows)
    assert autotune.best_config("norm_act", chip=s["chip"],
                                path=cache) == na["best"]["config"]
    # losers are recorded too, with prune reasons where applicable
    pruned = [r for r in rows if r.get("pruned")]
    assert pruned, "pruned candidates must land in the jsonl as losers"


# ---------------------------------------------------------------------------
# trace_report --view tune
# ---------------------------------------------------------------------------

def test_tune_view_strikes_invalid_rows(tmp_path):
    import trace_report

    path = str(tmp_path / "rows.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(
            {"experiment": "autotune:fake:good", "site": "fake",
             "candidate": "good", "config": {"tile": 128},
             "step_time_ms": 2.0, "best": True, "valid": True}) + "\n")
        f.write(json.dumps(
            {"experiment": "autotune:fake:liar", "site": "fake",
             "candidate": "liar", "config": {"tile": 256},
             "step_time_ms": 1.0, "mfu_pct": 1095.0,
             "valid": False, "invalid_reason": "impossible"}) + "\n")
        f.write("not json\n")
    rows = trace_report.load_tune_rows(path)
    assert len(rows) == 2
    out = trace_report.render_tune(rows)
    assert "BEST" in out
    # the invalid row is struck through (combining stroke), not dropped
    assert "INVALID" in out
    assert "l̶i̶a̶r̶" in out
    assert "good" in out


def test_tune_view_empty():
    import trace_report

    assert "no autotune rows" in trace_report.render_tune([])


# ---------------------------------------------------------------------------
# one-dispatch regression pin: tuned kernels inside the fused step
# ---------------------------------------------------------------------------

def test_fused_step_one_dispatch_with_pallas_conv(monkeypatch):
    """dispatches_per_step must stay exactly 1.0 with the tuned conv
    backward in the trace — the whole point of trace-time config
    consultation. The pallas path is asserted really taken (not a
    silent per-layer fallback) by spying on conv2d."""
    import mxnet_tpu as mx
    from mxnet_tpu import symbol as sym
    from mxnet_tpu import telemetry
    from mxnet_tpu.module import Module
    from mxnet_tpu.ops import pallas_kernels as pk

    if not pk.pallas_available():
        pytest.skip("pallas unavailable")
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_TPU_PALLAS_CONV", "1")

    taken = []
    orig = pk.conv2d

    def spy(*a, **kw):
        out = orig(*a, **kw)
        taken.append(out is not None)
        return out

    monkeypatch.setattr(pk, "conv2d", spy)

    batch, c, h, nb = 2, 128, 8, 4
    net = sym.Variable("data")
    net = sym.Convolution(net, kernel=(3, 3), num_filter=c, pad=(1, 1),
                          no_bias=True, name="conv1")
    net = sym.Activation(net, act_type="relu")
    net = sym.Flatten(net)
    net = sym.FullyConnected(net, num_hidden=3, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")

    rng = np.random.RandomState(0)
    X = rng.randn(batch * nb, c, h, h).astype(np.float32)
    y = rng.randint(0, 3, batch * nb).astype(np.float32)
    data = mx.io.NDArrayIter(X, y, batch_size=batch)

    telemetry.reset()
    telemetry.enable()
    try:
        before = telemetry.peek("step.dispatches") or 0
        mod = Module(net, context=mx.cpu())
        mod.fit(data, num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.01})
        delta = (telemetry.peek("step.dispatches") or 0) - before
    finally:
        telemetry.reset()
        telemetry.disable()
    assert mod._fused_step_active
    assert delta / nb == 1.0
    assert taken and all(taken), \
        "the pallas conv backward must actually be in the fused trace"
