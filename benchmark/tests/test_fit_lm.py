"""CPU rehearsals of the ``fit_lm`` driver at toy width (``toy_lm.py``): a
sound run is ``correct``; a capacity that drops rows, a halved learning
rate and a switched-off update are not; a traced run reports every
per-layer metric the cell lists; the reference's count of operations and
bytes against hand-worked values; the scope reader on a hand-made
``.xplane.pb`` and on a recorded cut of the cell's trace."""
import gzip
import json
import os
import struct
import time

import pytest

import toy_lm
from benchmark import harness
from benchmark.drivers import fit_lm
from benchmark.reference import nemotron_h as ref
from benchmark.trace import scopes

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def on_cpu(monkeypatch):
    import jax

    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "flops_per_s": {"float32": 1e12, "bfloat16": 2e12},
        "hbm_bytes_per_s": 1e11})
    for key in ("MXNET_COMPUTE_DTYPE", "MXNET_TPU_FUSED_STEP",
                "MXNET_BACKWARD_DO_MIRROR"):
        monkeypatch.setenv(key, "")
        monkeypatch.delenv(key)


def _run(cell, capsys, seed=3000000019, seconds=0.5, trace=False):
    rows = fit_lm.run(cell, seed=seed, seconds=seconds, trace=trace,
                      t_start=time.perf_counter() - 1000.0)["rows"]
    out = capsys.readouterr().out.strip().splitlines()
    return rows, json.loads(out[-1]), out


def _failed(rows):
    return {name for name, value, limit, _ in rows if value > limit}


def test_sound_run_is_correct_and_sits_on_the_reference(on_cpu, capsys):
    rows, line, out = _run(toy_lm.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["metrics"]["setup_s"]["value"] < 1000.0
    got = {r[0]: r[1] for r in rows}
    assert got.pop("step1_excess_noise") == pytest.approx(-1.0, abs=1e-3)
    assert got.pop("window_loss_over_first_loss") < 1.0
    assert all(v < 2e-3 for v in got.values()), rows
    # every held expert's slice is a leaf of its own
    assert any("tokens/s" in l for l in out)


def test_a_capacity_that_drops_rows_is_not_correct(on_cpu, capsys,
                                                   monkeypatch):
    """The routed-expert op given a capacity underneath the harness: the
    rows past 1.25x the mean load get no slot."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import moe

    plan = moe.plan

    def capped(eid, wts, first_held, num_held, block):
        s, k = eid.shape
        cap = -(-5 * s * k // (4 * 16))          # 1.25 x rows x k / experts
        onehot = (eid[..., None] == jnp.arange(16)).any(axis=1)
        rank = jnp.cumsum(onehot, axis=0)        # a row's place at an expert
        keep = jnp.take_along_axis(rank, eid, axis=1) <= cap
        return plan(jnp.where(keep, eid, 10 ** 6), wts, first_held, num_held,
                    block)

    monkeypatch.setattr(moe, "plan", capped)
    rows, line, _ = _run(toy_lm.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert "step1_excess_noise" in _failed(rows)


def test_half_the_learning_rate_is_not_correct(on_cpu, capsys, monkeypatch):
    """Adam's first moment does not know the learning rate; the change
    over three steps does."""
    import mxnet_tpu as mx

    fit_ = mx.mod.Module.fit

    def wrong(self, *args, **kw):
        kw["optimizer_params"] = dict(
            kw["optimizer_params"],
            learning_rate=kw["optimizer_params"]["learning_rate"] / 2)
        return fit_(self, *args, **kw)

    monkeypatch.setattr(mx.mod.Module, "fit", wrong)
    rows, line, _ = _run(toy_lm.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert "delta_norm_median_leaf_gap" in _failed(rows)
    assert "grad_norm_median_leaf_gap" not in _failed(rows)
    assert "dead_leaves" not in _failed(rows)


def test_a_switched_off_update_is_not_correct(on_cpu, capsys, monkeypatch):
    from mxnet_tpu import optimizer

    monkeypatch.setattr(optimizer, "_update_math",
                        lambda kind, n, clipped: (
                            lambda w, g, states, s: (w, states)))
    rows, line, _ = _run(toy_lm.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert {"delta_norm_median_leaf_gap", "dead_leaves"} <= _failed(rows)


def test_a_selection_bias_that_never_moves_is_not_correct(on_cpu, capsys,
                                                          monkeypatch):
    """The experts' selection biases are states the step moves itself;
    they are compared as leaves beside the parameters."""
    from mxnet_tpu.ops import moe

    monkeypatch.setattr(moe, "balance_step", lambda bias, load, rate: bias)
    rows, line, _ = _run(toy_lm.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert _failed(rows) == {"dead_leaves"}
    assert "experts_select_bias" in [r for r in rows
                                     if r[0] == "dead_leaves"][0][3]


def test_the_run_starts_from_balanced_experts(on_cpu, capsys):
    """``init.balance``: before step 1 the family's balancing rule has run
    on the ring's last batch until it settled; without it the seeded
    router loads a few experts several times over."""
    def largest_over_mean(cell):
        _, _, out = _run(cell, capsys)
        line = [l for l in out if l.startswith("balanced start")]
        return [float(p.split(" / ")[0]) / float(p.split(" / ")[1])
                for p in line[0].split(": ")[-1].split("  ")] if line else None

    cell = toy_lm.cell(compute_dtype="float32")
    assert max(largest_over_mean(cell)) < 1.35
    del cell["config"]["init"]["balance"]
    assert largest_over_mean(cell) is None


def test_traced_run_reports_every_per_layer_metric(on_cpu, capsys,
                                                   monkeypatch):
    """``--trace 1`` on the CPU, the reduction lent one device event and
    the scope reader a scope for each part (the numbers mean nothing
    here): every per-layer metric the cell lists is on the line, the
    counters among them from the device's own counts."""
    from benchmark.trace import reduce as R

    real = R.reduce
    lent = {}

    def with_a_device_plane(trace, steps):
        lent["start"] = start = R.host_spans(trace)[0][1]
        trace["planes"].append({"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops",
             "events": [["fusion.1", "fusion:kOutput", start, 1e6]]}]})
        return real(trace, steps)

    def fake_scopes(path):
        t = lent["start"]
        paths = ["jit(step)/fwd/SSMScan:layer0_scan/dot_general",
                 "jit(step)/bwd/FullyConnected:layer0_in_proj/dot_general",
                 "jit(step)/fwd/RoutedExperts:layer1_experts/while",
                 "jit(step)/fwd/FullyConnected:layer1_shared_up/dot",
                 "jit(step)/bwd/CausalAttention:layer7_attn/dot_general",
                 "jit(step)/fwd/FullyConnected:lm_head/dot_general",
                 "jit(step)/update/mul"]
        return [(0, [("fusion.%d" % i, p, t + 1e5 * i, 5e4)
                     for i, p in enumerate(paths)])]

    monkeypatch.setattr(R, "reduce", with_a_device_plane)
    monkeypatch.setattr(scopes, "load", fake_scopes)
    cell = toy_lm.cell(compute_dtype="float32")
    rows, line, out = _run(cell, capsys, seed=7, seconds=3.0, trace=True)
    want = set(harness.metric_names(cell["spec"], "per_layer",
                                    "nemotron3_nano_fit_packed8k"))
    assert len(want) == 28 and set(line["metrics"]) == want
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["moe_dropped_rows_per_step"] == 0.0
    # the general readers of the layers the cell runs read it too
    assert m["fit_dispatches_per_step"] == 1.0
    assert m["step_compiles_in_window"] == 0.0 and m["step_jit_entries"] == 1
    assert m["input_h2d_bytes_per_step"] == 0.0
    tokens = 2 * toy_lm.ARGS["seq_len"]
    assert 0 < m["moe_rows_here_per_step"] <= 4 * tokens * 3
    assert m["moe_expert_load_max_over_mean"] >= 1.0
    assert m["ssm_scan_ms_per_step"] > 0 and m["attention_ms_per_step"] > 0
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert any(l.startswith("lowering: lower.scan_kernel.xla_chunked")
               for l in out)


# ---------------------------------------------------------------------------
# the reference's count of operations and bytes, against hand-worked values
# ---------------------------------------------------------------------------
FULL = dict(pattern="MEMEMEM*E", vocab=16384, experts_held=8)


def test_cost_of_one_mamba_layer_by_hand():
    tokens = 16384
    got = ref.layer_cost("M", FULL, tokens)
    # in 2688 -> 10304, out 4096 -> 2688, conv 6144 channels x 4 taps
    proj = 2 * tokens * 2688 * 10304 + 2 * tokens * 4096 * 2688 \
        + 2 * tokens * 6144 * 4
    assert got["ssm_proj_conv"][0] == proj == 1269162835968
    # C B^T: 128 x 128 a group and position; M x: 128 x 64 a head; the
    # chunk's state and C S: 64 x 128 a head, twice
    scan = 2 * tokens * 128 * 128 * 8 + 2 * tokens * 128 * 64 * 64 \
        + 4 * tokens * 64 * 128 * 64
    assert got["ssm_scan"][0] == scan == 55834574848
    assert got["ssm_scan"][1] == tokens * (2 * 4096 + 2 * 1024) * 2 \
        + tokens * 64 * 4 + 2 * 128 * 64 * 64 * 128 * 4


def test_cost_of_one_expert_layer_by_hand():
    tokens = 16384
    got = ref.layer_cost("E", FULL, tokens)
    rows = tokens * 6 * 8 // 128
    assert rows == 6144
    assert got["moe_grouped_matmul"][0] == 4 * rows * 2688 * 1856
    assert got["moe_rest"][0] == 2 * tokens * 2688 * 128 \
        + 4 * tokens * 2688 * 3712
    skewed = ref.layer_cost("E", FULL, tokens, rows_here=10000)
    assert skewed["moe_grouped_matmul"][0] == 4 * 10000 * 2688 * 1856


def test_cost_of_the_attention_layer_by_hand():
    tokens = 16384
    got = ref.layer_cost("*", FULL, tokens)
    proj = 2 * tokens * 2688 * (4096 + 512) + 2 * tokens * 4096 * 2688
    # two sequences; scores and weighted sum 2 T^2 D a head each; half
    attn = 2 * (2 * 2 * 8192 * 8192 * 128 * 32) // 2
    assert got["attention_proj"][0] == proj
    assert got["attention_kernel"][0] == attn == 1099511627776


def test_step_cost_counts_three_passes_and_states_the_recompute():
    cost = ref.step_cost(FULL, 2)
    fwd = sum(f for f, _ in cost["parts"].values()) // 3
    assert cost["flops"] == 3 * fwd and cost["recompute_flops"] == fwd
    assert cost["params"] == 666963456
    assert cost["state_bytes"] == cost["params"] * 30
    # ~2.1 GFLOP a token, as ISSUE 26 reckons
    assert 1.9e9 < cost["flops"] / 16384 < 2.4e9


# ---------------------------------------------------------------------------
# the scope reader
# ---------------------------------------------------------------------------
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _xspace(events, ref_values=False):
    """A one-plane XSpace whose "XLA Ops" line holds ``events``:
    ``[(name, scope, offset_ps, dur_ps)]``."""
    stat_meta = _field(5, _field(1, 1) + _field(2, _field(1, 1)
                                                + _field(2, "tf_op")))
    metas, evs = b"", b""
    for i, (name, scope, off, dur) in enumerate(events, 1):
        if ref_values:
            stat_meta += _field(5, _field(1, 100 + i) + _field(
                2, _field(1, 100 + i) + _field(2, scope)))
            stat = _field(1, 1) + _field(7, 100 + i)
        else:
            stat = _field(1, 1) + _field(5, scope)
        meta = _field(1, i) + _field(2, name) \
            + (_field(5, stat) if scope else b"")
        metas += _field(4, _field(1, i) + _field(2, meta))
        evs += _field(4, _field(1, i) + _field(2, off) + _field(3, dur))
    line = _field(2, "XLA Ops") + _field(3, 1000) + evs
    other = _field(2, "Steps") + _field(3, 1000) + evs
    plane = _field(2, "/device:TPU:0") + _field(3, line) + _field(3, other) \
        + metas + stat_meta
    host = _field(2, "/host:CPU") + _field(3, line)
    return _field(1, plane) + _field(1, host)


@pytest.mark.parametrize("ref_values", [False, True])
def test_scope_reader_reads_the_wire_format(tmp_path, ref_values):
    events = [("%while.1 = while(...)", "jit(step)/fwd/RoutedExperts:"
               "layer1_experts/while", 0, 10_000_000),
              ("%fusion.2", "jit(step)/fwd/RoutedExperts:layer1_experts/"
               "while/body/dot_general", 1_000_000, 4_000_000),
              ("%copy.3", "", 6_000_000, 1_000_000),
              ("%fusion.4", "jit(step)/bwd/transpose(jvp(SSMScan:"
               "layer0_scan))/dot_general", 20_000_000, 2_000_000),
              ("%fusion.5", "jit(step)/update/mul", 30_000_000, 1_000_000)]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(events, ref_values))
    (dev, got), = scopes.load(str(path))
    assert dev == 0 and len(got) == 5
    assert got[1][1].endswith("while/body/dot_general")
    assert got[0][2] == pytest.approx(1000 + 0.0) and got[1][3] == 4000.0
    # the while's own time is what its body leaves of it
    own = {n: t for n, _, t in scopes.self_times(got)}
    assert own["%while.1 = while(...)"] == pytest.approx(5000.0)
    assert scopes.split(got[3][1]) == ("bwd", "SSMScan", "layer0_scan")
    parts = scopes.by_part(got, 0, 1e12, fit_lm.part_of("MEMEMEM*E"))
    assert parts["moe_grouped_matmul"] == pytest.approx(9e-6)
    assert parts["ssm_scan"] == pytest.approx(2e-6)
    assert parts["optimizer"] == pytest.approx(1e-6)
    assert parts["(no scope)"] == pytest.approx(1e-6)


def test_part_of_names_every_layer_by_its_kind():
    part = fit_lm.part_of("MEMEMEM*E")
    assert part("fwd", "RMSNorm", "layer0_norm") == "ssm_proj_conv"
    assert part("bwd", "SSMScan", "layer6_scan") == "ssm_scan"
    assert part("bwd", "FullyConnected", "layer3_shared_down") == "moe_rest"
    assert part("fwd", "RoutedExperts", "layer8_experts") \
        == "moe_grouped_matmul"
    assert part("fwd", "FullyConnected", "layer7_q") == "attention_proj"
    assert part("bwd", "CausalAttention", "layer7_attn") \
        == "attention_kernel"
    assert part("fwd", "SoftmaxOutput", "softmax") == "lm_head_loss"
    assert part("metric", "", "") == "lm_head_loss"
    assert part("fwd", "_Plus", "_plus3") == "other:_Plus"


def test_scope_reader_on_a_recorded_cut_of_the_cells_trace():
    """Two steps of ``nemotron3_nano_fit_packed8k`` on the v5e, cut by
    ``tools/record_scopes.py``: device time by part as the run printed
    it."""
    path = os.path.join(HERE, "data", "nemotron3_nano_fit_packed8k")
    if not os.path.exists(path + ".scopes.json.gz"):
        pytest.skip("no recorded cut yet")
    with gzip.open(path + ".scopes.json.gz", "rt") as f:
        cut = json.load(f)
    with open(path + ".scopes.expect.json") as f:
        expect = json.load(f)
    got = scopes.by_part([tuple(e) for e in cut["events"]], cut["lo"],
                         cut["hi"], fit_lm.part_of(expect["pattern"]))
    assert set(got) == set(expect["by_part_s"])
    for part, seconds in expect["by_part_s"].items():
        assert got[part] == pytest.approx(seconds, rel=1e-6)
    for must in ("ssm_scan", "ssm_proj_conv", "moe_grouped_matmul",
                 "moe_rest", "attention_proj", "attention_kernel",
                 "lm_head_loss", "optimizer"):
        assert got[must] > 0
