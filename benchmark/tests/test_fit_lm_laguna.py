"""CPU rehearsals of the ``fit_lm_ref`` driver over the ``laguna`` reference
at toy width (``toy_lm_laguna.py``): a sound run is ``correct`` and starts
from selection biases the balancing rule has evened; a program whose windowed layers
read every key underneath is not; every one
of the reference's controls reads above the stated precision's floor; ONE
traced run, shared by the cases that only read it, reports every per-layer
metric the cell lists with the lowerings, the band's block pairs and the
experts' counters; ``part_of`` names every node of the toy net; the new
readers find nothing in a program without the parts."""
import contextlib
import io
import json
import time

import numpy as np
import pytest

import toy_lm_laguna
from benchmark import harness
from benchmark.drivers import fit_lm_ref
from benchmark.reference import laguna as ref
from benchmark.trace import scopes
from test_fit_lm import _failed, on_cpu  # noqa: F401

CONTROLS = ref.CONTROLS


def _run(cell, capsys, seed=3000000019, seconds=0.5, controls=()):
    res = fit_lm_ref.run(cell, seed=seed, seconds=seconds, trace=False,
                         t_start=time.perf_counter() - 1000.0,
                         controls=controls)
    out = capsys.readouterr().out.strip().splitlines()
    return res, json.loads(out[-1]), out


def test_a_causal_mask_underneath_is_not_correct(on_cpu, capsys,
                                                 monkeypatch):
    """The program's windowed layers reading EVERY earlier key (the six
    older cells' mask) underneath the reference that reads the last 16: the
    first step's log-probabilities already say so."""
    import mxnet_tpu.models as models

    build = models.get_laguna
    monkeypatch.setattr(models, "get_laguna",
                        lambda **kw: build(**dict(kw, window=0)))
    res, line, _ = _run(toy_lm_laguna.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert "step1_excess_noise" in _failed(res["rows"])


def test_part_of_names_every_node_of_the_toy_net():
    """Every operator node of the toy net, in every phase, belongs to a part
    the readers know; only the embedding's lookup and the two reshapes that
    name no layer are ``other``. The windowed layers' op is a part of its
    own."""
    from mxnet_tpu.models import get_laguna

    part = ref.part_of(toy_lm_laguna.ARGS)
    parts, other = {}, []
    for node in get_laguna(**toy_lm_laguna.ARGS)._topo():
        if node.is_variable:
            continue
        op = type(node.op).op_name
        for phase in ("fwd", "bwd"):
            name = part(phase, op, node.name)
            parts.setdefault(name, set()).add(node.name)
            if name.startswith("other:"):
                other.append(node.name)
    assert set(parts) - {p for p in parts if p.startswith("other:")} == {
        "attention_proj", "attention_kernel", "attention_window_kernel",
        "dense_ffn", "moe_grouped_matmul", "moe_rest", "lm_head_loss"}
    assert {n for n in other if n.startswith("layer")} == set()
    assert "embed" in other
    assert parts["attention_kernel"] == {"layer0_attn", "layer2_attn"}
    assert parts["attention_window_kernel"] == {"layer1_attn"}
    assert {"layer0_mixer_norm", "layer0_q", "layer0_k", "layer0_v",
            "layer0_g", "layer0_g_act", "layer0_g_heads",
            "layer0_attn_heads", "layer0_gated", "layer0_gated_rows",
            "layer0_o", "layer0_mixer_add", "layer1_q", "layer2_g_act",
            "layer1_gated", "layer1_mixer_norm"} <= parts["attention_proj"]
    assert parts["dense_ffn"] == {
        "layer0_ffn_norm", "layer0_ffn_gate", "layer0_ffn_act",
        "layer0_ffn_up", "layer0_ffn_mul", "layer0_ffn_down",
        "layer0_ffn_add"}
    assert parts["moe_grouped_matmul"] == {
        "layer%d_ffn_experts" % i for i in (1, 2)}
    assert {"layer1_ffn_norm", "layer1_ffn_shared_up",
            "layer1_ffn_shared_down", "layer1_ffn_sum",
            "layer1_ffn_add"} <= parts["moe_rest"]
    assert part("update", "", "") == "optimizer"
    assert part("metric", "", "") == "lm_head_loss"


# ---------------------------------------------------------------------------
# one traced run, read by several cases
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced():
    """``--trace 1`` on the CPU in float32, the reduction lent one device
    event and the scope reader a scope for each part (the numbers mean
    nothing here), and the reference's controls read on the run's own
    weights and batch: ONE run of the driver for the five cases below."""
    import jax

    from benchmark.trace import reduce as R

    real, lent = R.reduce, {}

    def with_a_device_plane(trace, steps):
        lent["start"] = start = R.host_spans(trace)[0][1]
        trace["planes"].append({"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops",
             "events": [["fusion.1", "fusion:kOutput", start, 1e6]]}]})
        return real(trace, steps)

    def fake_scopes(path):
        t = lent["start"]
        paths = ["jit(step)/fwd/CausalAttention:layer0_attn/dot_general",
                 "jit(step)/bwd/CausalAttention:layer1_attn/dot_general",
                 "jit(step)/fwd/FullyConnected:layer2_q/dot_general",
                 "jit(step)/bwd/FullyConnected:layer0_ffn_up/dot_general",
                 "jit(step)/fwd/RoutedExperts:layer1_ffn_experts/while",
                 "jit(step)/bwd/FullyConnected:layer2_ffn_shared_up/dot",
                 "jit(step)/fwd/FullyConnected:lm_head/dot_general",
                 "jit(step)/update/mul"]
        return [(0, [("fusion.%d" % i, p, t + 1e5 * i, 5e4)
                     for i, p in enumerate(paths)])]

    cell = toy_lm_laguna.cell(compute_dtype="float32")
    out = io.StringIO()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "require_chips", lambda n: jax.devices()[:n])
        mp.setattr(harness, "peaks", lambda kind: {
            "flops_per_s": {"float32": 1e12, "bfloat16": 2e12},
            "hbm_bytes_per_s": 1e11})
        for key in cell["config"]["env"]:
            mp.setenv(key, "")
            mp.delenv(key)
        mp.setattr(R, "reduce", with_a_device_plane)
        mp.setattr(scopes, "load", fake_scopes)
        with contextlib.redirect_stdout(out):
            res = fit_lm_ref.run(cell, seed=7, seconds=3.0, trace=True,
                                 t_start=time.perf_counter() - 1000.0,
                                 controls=CONTROLS)
    lines = out.getvalue().strip().splitlines()
    return cell, res, json.loads(lines[-1]), lines


def test_sound_run_is_correct_and_sits_on_the_reference(traced):
    _, res, line, out = traced
    assert line["correct"] is True and line["failed"] == 0
    got = {r[0]: r[1] for r in res["rows"]}
    assert got.pop("step1_excess_noise") == pytest.approx(-1.0, abs=1e-3)
    assert got.pop("window_loss_over_first_loss") < 1.0
    # float32 on both sides, the band of blocks against the written-out
    # mask, grouped experts against a masked loop: every gap under 2e-3
    assert all(v < 2e-3 for v in got.values()), res["rows"]
    # every parameter is a leaf that was compared (each held expert's slice
    # of the stacked weights its own), and the selection biases, which the
    # step moves itself, among the leaves of the change
    shapes = ref.param_shapes(toy_lm_laguna.ARGS)
    want = set(ref.leaf_norms({k: np.zeros(s) for k, s in shapes.items()}))
    states = {k for k in want if k.endswith(ref.STATE)}
    assert states == {"layer%d_ffn_experts_select_bias" % i for i in (1, 2)}
    assert set(res["want"]["delta_norms"]) == want
    assert set(res["want"]["grad_norms"]) == want - states
    assert set(res["got"]["delta_norms"]) == want
    assert all(res["got"]["delta_norms"][k] > 0 for k in states)
    assert {"layer0_g_weight", "layer1_g_weight", "layer0_ffn_up_weight",
            "layer1_ffn_experts_router_weight",
            "layer2_ffn_shared_down_weight", "layer2_q_weight"} <= want
    # the reference balanced the experts before step 1, inside init_params
    start = [l for l in out if l.startswith("balanced start:")]
    assert len(start) == 1
    pairs = start[0].split("by layer: ")[1].split("  ")
    assert len(pairs) == 2
    for pair in pairs:
        most, mean = (float(x) for x in pair.split(" / "))
        assert most <= 1.5 * mean


def test_the_controls_read_above_the_stated_precisions_floor(traced):
    """The reference in the program's place, the bfloat16 pipeline with one
    thing wrong, on the run's own weights and batch: each of the ten reads
    above the limit, which a sound program (float32 here: -1, nearer the
    reference than the stated precision's floor) reads far under."""
    _, res, _, out = traced
    sound = {r[0]: r[1] for r in res["rows"]}["step1_excess_noise"]
    assert sound < 1.0
    reads = res["controls"]
    assert set(reads) == set(CONTROLS) and len(CONTROLS) == 10
    for name in CONTROLS:
        assert not reads[name] <= 1.0, (name, reads[name])
    lines = [l for l in out if l.startswith("control ")]
    assert len(lines) == len(CONTROLS)
    assert all("fails, as it must" in l for l in lines)


def test_traced_run_reports_every_per_layer_metric(traced):
    """The cell's own count: the 16 metrics every cell reports, the twelve by
    part or counter that the older language cells share with it, and the
    three readers this configuration brought. The toy's attentions take the
    XLA body, which counts no block pairs: the share falls silent here as it
    does on a program without the counter."""
    cell, _, line, _ = traced
    want = set(harness.metric_names(cell["spec"], "per_layer",
                                    toy_lm_laguna.CELL))
    new = {"attention_window_ms_per_step", "attention_window_roofline",
           "attention_window_block_pairs_share"}
    assert len(want) == 31 and new <= want
    assert set(line["metrics"]) == want - {
        "attention_window_block_pairs_share"}
    assert not {"mla_proj_ms_per_step", "linattn_scan_ms_per_step",
                "moe_expert_load_max_over_mean"} & want
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["fit_dispatches_per_step"] == 1.0
    assert m["step_compiles_in_window"] == 0.0 and m["step_jit_entries"] == 1
    assert m["input_h2d_bytes_per_step"] == 0.0
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_traced_run_reads_the_parts_by_scope(traced):
    """One lent event a part: the windowed layers' op under its own reader,
    the full layers' op with both kinds' projections under
    ``attention_ms_per_step``, the experts' two parts together and what lies
    beside the op alone, the dense layer apart; every roofline the cell
    lists reads something, and none over 100%."""
    _, _, line, out = traced
    m = {k: v["value"] for k, v in line["metrics"].items()}
    lent_ms = m["attention_window_ms_per_step"]
    assert lent_ms > 0
    for name in ("attn_proj_ms_per_step", "dense_ffn_ms_per_step",
                 "moe_rest_ms_per_step", "lm_head_loss_ms_per_step"):
        assert m[name] == pytest.approx(lent_ms)
    for name in ("attention_ms_per_step", "moe_experts_ms_per_step"):
        assert m[name] == pytest.approx(2 * lent_ms)
    for name in ("attention_window_roofline", "attn_proj_roofline",
                 "attention_roofline", "moe_grouped_matmul_roofline",
                 "lm_step_roofline"):
        assert m[name] > 0
    assert any(l.startswith("roofline attention_window_kernel") for l in out)
    assert any(l.startswith("roofline attention_kernel") for l in out)


def test_traced_run_counts_lowerings_and_routed_rows(traced):
    """The lowering counters once a traced node, and the experts' rows as
    the device counted them: every pair of every expert layer, none
    dropped."""
    _, _, line, out = traced
    args = toy_lm_laguna.ARGS
    lowered = {l.split()[1]: int(l.split()[3]) for l in out
               if l.startswith("lowering: ")}
    assert lowered == {"lower.attention_mask.causal": 2,
                       "lower.attention_mask.window": 1,
                       "lower.attention_kernel.xla_blockwise": 3,
                       "lower.experts_score.sigmoid": 2,
                       "lower.experts_body.swiglu": 2,
                       "lower.experts_kernel.xla_loop": 2}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["moe_dropped_rows_per_step"] == 0.0
    pairs = 2 * 2 * args["seq_len"] * args["top_k"]
    assert 0 < m["moe_rows_here_per_step"] < pairs


def test_the_new_readers_find_nothing_in_a_program_without_the_parts():
    """On a trace whose scopes name none of this model's parts and in a
    program that counts no band (the parent, another model) the new readers
    return nothing and do not raise; where the counters stand, the share is
    their quotient (the cell's: three windowed ops of 31 pairs over 136)."""
    from mxnet_tpu import telemetry

    trace = {"steps": 4, "busy_s": 1.0,
             "scope_s": {"attention_kernel": 0.1, "optimizer": 0.2}}
    cell = {"step_parts": {"attention_kernel": (1e9, 1e6)},
            "peaks": {"flops_per_s": {"bfloat16": 1e12},
                      "hbm_bytes_per_s": 1e11},
            "config": {"compute_dtype": "bfloat16"}}
    telemetry.reset()
    readers = {name: harness.importlib.import_module(
        "benchmark.metrics." + name) for name in (
            "attention_window_ms_per_step", "attention_window_roofline",
            "attention_window_block_pairs_share")}
    for name, empty in (("attention_window_ms_per_step", 0.0),
                        ("attention_window_roofline", None),
                        ("attention_window_block_pairs_share", None)):
        assert readers[name].read(trace, {}, [], cell) == empty
        assert readers[name].read({"steps": 4}, {}, [], {}) is None
    telemetry.enable()
    try:
        telemetry.inc("lower.attention_window.block_pairs", 3 * 31)
        telemetry.inc("lower.attention_window.block_pairs_causal", 3 * 136)
        assert readers["attention_window_block_pairs_share"].read(
            trace, {}, [], cell) == pytest.approx(31 / 136)
    finally:
        telemetry.disable()
        telemetry.reset()
