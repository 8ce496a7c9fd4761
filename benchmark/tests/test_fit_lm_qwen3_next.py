"""CPU rehearsals of the ``fit_lm_ref`` driver over the ``qwen3_next``
reference at toy width (``toy_lm_qwen3_next.py``): a sound run is ``correct``
and starts from routers the auxiliary loss has balanced; a switched-off
update, a halved learning rate and a sigmoid router underneath are not;
every one of the reference's controls that a forward pass can read reads
above the stated precision's floor, and ``no_aux_loss`` reads the floor
itself; ONE traced run, shared by the cases that only read it, reports every
per-layer metric the cell lists with the lowerings and the experts'
counters; ``part_of`` names every node of the toy net."""
import contextlib
import io
import json
import time

import numpy as np
import pytest

import toy_lm_qwen3_next
from benchmark import harness
from benchmark.drivers import fit_lm_ref
from benchmark.reference import qwen3_next as ref
from benchmark.trace import scopes
from test_fit_lm import _failed, on_cpu  # noqa: F401

CONTROLS = ref.CONTROLS


def _run(cell, capsys, seed=3000000019, seconds=0.5, controls=()):
    res = fit_lm_ref.run(cell, seed=seed, seconds=seconds, trace=False,
                         t_start=time.perf_counter() - 1000.0,
                         controls=controls)
    out = capsys.readouterr().out.strip().splitlines()
    return res, json.loads(out[-1]), out


def test_sound_run_is_correct_and_sits_on_the_reference(on_cpu, capsys):
    res, line, out = _run(toy_lm_qwen3_next.cell(compute_dtype="float32"),
                          capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    got = {r[0]: r[1] for r in res["rows"]}
    assert got.pop("step1_excess_noise") == pytest.approx(-1.0, abs=1e-3)
    assert got.pop("window_loss_over_first_loss") < 1.0
    # float32 on both sides, the chunked delta rule against the recurrence,
    # grouped experts against a masked loop: every gap under 2e-3
    assert all(v < 2e-3 for v in got.values()), res["rows"]
    # every parameter is a leaf that was compared (each held expert's slice
    # of the stacked up and down weights its own); this family has no state
    # that a step moves itself
    shapes = ref.param_shapes(toy_lm_qwen3_next.ARGS)
    want = set(ref.leaf_norms({k: np.zeros(s) for k, s in shapes.items()}))
    assert set(res["want"]["delta_norms"]) == want
    assert set(res["want"]["grad_norms"]) == want
    assert {"layer0_delta_A_log", "layer0_delta_dt_bias", "layer2_q_weight",
            "layer2_qnorm_gamma", "layer2_knorm_gamma", "layer1_a_weight",
            "layer0_ffn_sgate_weight", "layer3_ffn_experts_router_weight",
            "layer0_gnorm_gamma"} <= want
    # the reference balanced the routers before step 1, inside init_params
    start = [l for l in out if l.startswith("balanced start:")]
    assert len(start) == 1
    pairs = start[0].split("by layer: ")[1].split(" (")[0].split("  ")
    assert len(pairs) == 4
    for pair in pairs:
        most, mean = (float(x) for x in pair.split(" / "))
        assert most <= 2.0 * mean


def test_a_switched_off_update_is_not_correct(on_cpu, capsys, monkeypatch):
    from mxnet_tpu import optimizer

    monkeypatch.setattr(optimizer, "_update_math",
                        lambda kind, n, clipped: (
                            lambda w, g, states, s: (w, states)))
    res, line, _ = _run(toy_lm_qwen3_next.cell(compute_dtype="float32"),
                        capsys)
    assert line["correct"] is False
    assert {"delta_norm_median_leaf_gap", "dead_leaves"} <= _failed(
        res["rows"])


def test_half_the_learning_rate_is_not_correct(on_cpu, capsys, monkeypatch):
    import mxnet_tpu as mx

    fit_ = mx.mod.Module.fit

    def wrong(self, *args, **kw):
        kw["optimizer_params"] = dict(
            kw["optimizer_params"],
            learning_rate=kw["optimizer_params"]["learning_rate"] / 2)
        return fit_(self, *args, **kw)

    monkeypatch.setattr(mx.mod.Module, "fit", wrong)
    res, line, _ = _run(toy_lm_qwen3_next.cell(compute_dtype="float32"),
                        capsys)
    assert line["correct"] is False
    assert "delta_norm_median_leaf_gap" in _failed(res["rows"])
    assert "grad_norm_median_leaf_gap" not in _failed(res["rows"])


def test_a_sigmoid_router_underneath_is_not_correct(on_cpu, capsys,
                                                    monkeypatch):
    """The program's router scoring by sigmoid (the four older expert cells'
    program) underneath the reference that takes a softmax over the 32
    experts: the same experts at other weights, and the first step's
    log-probabilities already say so."""
    from mxnet_tpu.ops import moe

    route = moe.route
    monkeypatch.setattr(moe, "route", lambda *a: route(*a[:9]))
    res, line, _ = _run(toy_lm_qwen3_next.cell(compute_dtype="float32"),
                        capsys)
    assert line["correct"] is False
    assert "step1_excess_noise" in _failed(res["rows"])


def test_the_controls_read_above_the_stated_precisions_floor(on_cpu, capsys):
    """The reference in the program's place, the bfloat16 pipeline with one
    thing wrong, on the run's own weights and batch: each reads above a
    sound bfloat16 program (which reads about 0), but two. ``no_aux_loss``:
    no forward pass reads the coefficient, it is the floor itself.
    ``state_bf16``: over the toy's 64 positions a state rounded at every
    position is within the noise of the few rows whose choice of experts
    flips (the floor's own swing between seeds here is larger than any
    limit); it is told apart at the cell's 8,192 positions (PERF.md
    section 2), and here the rounding is held to reach the output at all."""
    res, line, out = _run(toy_lm_qwen3_next.cell(), capsys, seed=3000000022,
                          controls=CONTROLS)
    sound = {r[0]: r[1] for r in res["rows"]}["step1_excess_noise"]
    assert sound < 1.0
    reads = res["controls"]
    assert set(reads) == set(CONTROLS) and len(CONTROLS) == 8
    assert reads["no_aux_loss"] == 0.0
    assert reads["state_bf16"] != 0.0
    told = [name for name in CONTROLS
            if name not in ("no_aux_loss", "state_bf16")]
    for name in told:
        assert not reads[name] <= 1.0, (name, reads[name])
    lines = [l for l in out if l.startswith("control ")]
    assert len(lines) == len(CONTROLS)
    assert sum("fails, as it must" in l for l in lines) >= len(told)


def test_part_of_names_every_node_of_the_toy_net():
    """Every operator node of the toy net, in every phase, belongs to a part
    the readers know; only the embedding's lookup and the two reshapes that
    name no layer are ``other``."""
    from mxnet_tpu.models import get_qwen3_next

    part = ref.part_of(toy_lm_qwen3_next.ARGS)
    parts, other = {}, []
    for node in get_qwen3_next(**toy_lm_qwen3_next.ARGS)._topo():
        if node.is_variable:
            continue
        op = type(node.op).op_name
        for phase in ("fwd", "bwd"):
            name = part(phase, op, node.name)
            parts.setdefault(name, set()).add(node.name)
            if name.startswith("other:"):
                other.append(node.name)
    assert set(parts) - {p for p in parts if p.startswith("other:")} == {
        "linattn_scan", "linattn_proj_conv", "attention_proj",
        "attention_kernel", "moe_grouped_matmul", "moe_rest", "lm_head_loss"}
    assert {n for n in other if n.startswith("layer")} == set()
    assert "embed" in other
    assert parts["linattn_scan"] == {"layer%d_delta" % i for i in (0, 1, 3)}
    assert parts["linattn_proj_conv"] >= {
        "layer0_mixer_norm", "layer0_q", "layer0_qconv", "layer0_a",
        "layer0_b", "layer0_g", "layer0_gnorm", "layer0_o",
        "layer0_mixer_add", "layer3_kconv_act"}
    assert parts["attention_kernel"] == {"layer2_attn"}
    assert {"layer2_mixer_norm", "layer2_q", "layer2_q_heads",
            "layer2_q_query", "layer2_q_gate", "layer2_q_gate_act",
            "layer2_qnorm", "layer2_k", "layer2_knorm", "layer2_v",
            "layer2_gated", "layer2_o", "layer2_mixer_add"} \
        <= parts["attention_proj"]
    assert parts["moe_grouped_matmul"] == {
        "layer%d_ffn_experts" % i for i in range(4)}
    assert {"layer1_ffn_norm", "layer1_ffn_shared_up", "layer1_ffn_sgate",
            "layer1_ffn_sgate_act", "layer1_ffn_shared_gated",
            "layer1_ffn_sum", "layer1_ffn_add"} <= parts["moe_rest"]
    assert part("update", "", "") == "optimizer"
    assert part("metric", "", "") == "lm_head_loss"


# ---------------------------------------------------------------------------
# one traced run, read by several cases
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced():
    """``--trace 1`` on the CPU, the reduction lent one device event and the
    scope reader a scope for each part (the numbers mean nothing here)."""
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
        paths = ["jit(step)/fwd/GatedDeltaRule:layer0_delta/while",
                 "jit(step)/bwd/FullyConnected:layer1_a/dot_general",
                 "jit(step)/fwd/FullyConnected:layer2_q/dot_general",
                 "jit(step)/bwd/CausalAttention:layer2_attn/dot_general",
                 "jit(step)/fwd/RoutedExperts:layer1_ffn_experts/while",
                 "jit(step)/bwd/FullyConnected:layer2_ffn_shared_up/dot",
                 "jit(step)/fwd/FullyConnected:lm_head/dot_general",
                 "jit(step)/update/mul"]
        return [(0, [("fusion.%d" % i, p, t + 1e5 * i, 5e4)
                     for i, p in enumerate(paths)])]

    cell = toy_lm_qwen3_next.cell(compute_dtype="float32")
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
                                 t_start=time.perf_counter() - 1000.0)
    lines = out.getvalue().strip().splitlines()
    return cell, res, json.loads(lines[-1]), lines


def test_traced_run_reports_every_per_layer_metric(traced):
    """The cell's own count: the 18 metrics every cell reports, the eleven
    by part or counter that the older language cells share with it, and the
    three readers this configuration brought."""
    cell, _, line, _ = traced
    want = set(harness.metric_names(cell["spec"], "per_layer",
                                    toy_lm_qwen3_next.CELL))
    assert len(want) == 30 and set(line["metrics"]) == want
    assert {"moe_rest_ms_per_step", "attn_proj_ms_per_step",
            "attn_proj_roofline"} <= want
    assert not {"mla_proj_ms_per_step", "dense_ffn_ms_per_step",
                "moe_expert_load_max_over_mean"} & want
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["fit_dispatches_per_step"] == 1.0
    assert m["step_compiles_in_window"] == 0.0 and m["step_jit_entries"] == 1
    assert m["input_h2d_bytes_per_step"] == 0.0
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_traced_run_reads_the_parts_by_scope(traced):
    """One lent event a part: the delta rule's op and its projections each
    under their reader, attention's two parts together and the projections'
    alone, the experts' two together and what lies beside the op alone;
    every roofline the cell lists reads something."""
    _, _, line, out = traced
    m = {k: v["value"] for k, v in line["metrics"].items()}
    lent_ms = m["linattn_scan_ms_per_step"]
    assert lent_ms > 0
    for name in ("linattn_proj_conv_ms_per_step", "attn_proj_ms_per_step",
                 "moe_rest_ms_per_step", "lm_head_loss_ms_per_step"):
        assert m[name] == pytest.approx(lent_ms)
    for name in ("attention_ms_per_step", "moe_experts_ms_per_step"):
        assert m[name] == pytest.approx(2 * lent_ms)
    for name in ("linattn_scan_roofline", "attn_proj_roofline",
                 "attention_roofline", "moe_grouped_matmul_roofline",
                 "lm_step_roofline"):
        assert m[name] > 0
    assert any(l.startswith("roofline linattn_scan") for l in out)
    assert any(l.startswith("roofline attention_proj") for l in out)


def test_traced_run_counts_lowerings_and_routed_rows(traced):
    """The lowering counters once a traced node, and the experts' rows as
    the device counted them: every pair of every expert layer, none
    dropped."""
    _, _, line, out = traced
    args = toy_lm_qwen3_next.ARGS
    lowered = {l.split()[1]: int(l.split()[3]) for l in out
               if l.startswith("lowering: ")}
    assert lowered == {"lower.delta_rule_heads.grouped": 3,
                       "lower.delta_rule_gate.head": 3,
                       "lower.delta_rule_kernel.xla_chunked": 3,
                       "lower.attention_kernel.xla_blockwise": 1,
                       "lower.experts_score.softmax": 4,
                       "lower.experts_body.swiglu": 4,
                       "lower.experts_kernel.xla_loop": 4}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["moe_dropped_rows_per_step"] == 0.0
    pairs = 4 * 2 * args["seq_len"] * args["top_k"]
    assert 0 < m["moe_rows_here_per_step"] < pairs
