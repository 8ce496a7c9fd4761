"""CPU rehearsals of the ``fit_lm_ref`` driver over the ``bailing_hybrid``
reference at toy width (``toy_lm_bailing.py``): a sound run is ``correct``
and starts from balanced experts; a switched-off update, a halved learning
rate, a decay a head underneath and a router without its group limit
underneath are not; every one of the reference's controls reads above the
stated precision's floor; ONE traced run, shared by the cases that only read
it, reports every per-layer metric the cell lists with the lowerings and the
experts' counters; ``part_of`` names every node of the toy net."""
import contextlib
import io
import json
import time

import numpy as np
import pytest

import toy_lm_bailing
from benchmark import harness
from benchmark.drivers import fit_lm_ref
from benchmark.reference import bailing_hybrid as ref
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
    res, line, out = _run(toy_lm_bailing.cell(compute_dtype="float32"),
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
    # of the stacked up and down weights its own), and the selection biases,
    # which the step moves itself, among the leaves of the change
    shapes = ref.param_shapes(toy_lm_bailing.ARGS)
    want = set(ref.leaf_norms({k: np.zeros(s) for k, s in shapes.items()}))
    assert set(res["want"]["delta_norms"]) == want
    states = {k for k in want if k.endswith(ref.STATE)}
    assert len(states) == 3
    assert set(res["want"]["grad_norms"]) == want - states
    assert all(res["got"]["delta_norms"][k] > 0 for k in states)
    assert {"layer0_delta_A_log", "layer0_delta_dt_bias", "layer2_gate_weight",
            "layer2_kv_b_weight", "layer1_a_weight"} <= want
    # the reference balanced the experts before step 1, inside init_params
    start = [l for l in out if l.startswith("balanced start:")]
    assert len(start) == 1
    for pair in start[0].split(": ")[-1].split("  "):
        most, mean = (float(x) for x in pair.split(" / "))
        assert most <= 1.5 * mean


def test_a_switched_off_update_is_not_correct(on_cpu, capsys, monkeypatch):
    from mxnet_tpu import optimizer

    monkeypatch.setattr(optimizer, "_update_math",
                        lambda kind, n, clipped: (
                            lambda w, g, states, s: (w, states)))
    res, line, _ = _run(toy_lm_bailing.cell(compute_dtype="float32"), capsys)
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
    res, line, _ = _run(toy_lm_bailing.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert "delta_norm_median_leaf_gap" in _failed(res["rows"])
    assert "grad_norm_median_leaf_gap" not in _failed(res["rows"])


def test_a_decay_a_head_underneath_is_not_correct(on_cpu, capsys,
                                                  monkeypatch):
    """The program's delta rule with a head's decays replaced by their mean
    (the sibling cell's mechanism under this model's name) underneath the
    reference that decays a channel: another model, and the first step's
    log-probabilities already say so."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import seq

    body = seq.gated_delta_chunked_channel

    def per_head(q, k, v, g, beta, chunk):
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        return body(q, k, v, g, beta, chunk)

    monkeypatch.setattr(seq, "gated_delta_chunked_channel", per_head)
    res, line, _ = _run(toy_lm_bailing.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert "step1_excess_noise" in _failed(res["rows"])


def test_a_router_without_its_group_limit_is_not_correct(on_cpu, capsys,
                                                         monkeypatch):
    """The program's router choosing the largest of ALL experts underneath
    the reference that keeps 2 of 4 groups: other experts for the rows whose
    best lie in a dropped group."""
    import functools

    from mxnet_tpu.ops import moe

    monkeypatch.setattr(moe, "route", functools.partial(
        lambda route, *a, **kw: route(*a[:7], **kw), moe.route))
    res, line, _ = _run(toy_lm_bailing.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert "step1_excess_noise" in _failed(res["rows"])


def test_the_controls_read_above_the_stated_precisions_floor(on_cpu, capsys):
    """The reference in the program's place, the bfloat16 pipeline with one
    thing wrong, on the run's own weights and batch: each reads above a
    sound bfloat16 program (which reads about 0)."""
    res, line, out = _run(toy_lm_bailing.cell(), capsys, controls=CONTROLS)
    sound = {r[0]: r[1] for r in res["rows"]}["step1_excess_noise"]
    assert sound < 1.0
    reads = res["controls"]
    assert set(reads) == set(CONTROLS) and len(CONTROLS) == 7
    for name in CONTROLS:
        assert not reads[name] <= 1.0, (name, reads[name])
    lines = [l for l in out if l.startswith("control ")]
    assert len(lines) == len(CONTROLS)
    assert all("fails, as it must" in l for l in lines)


def test_part_of_names_every_node_of_the_toy_net():
    """Every operator node of the toy net, in every phase, belongs to a part
    the readers know; only the embedding's lookup and the two reshapes that
    name no layer are ``other``."""
    from mxnet_tpu.models import get_bailing_hybrid

    part = ref.part_of(toy_lm_bailing.ARGS)
    parts, other = {}, []
    for node in get_bailing_hybrid(**toy_lm_bailing.ARGS)._topo():
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
        "attention_kernel", "dense_ffn", "moe_grouped_matmul", "moe_rest",
        "lm_head_loss"}
    assert {n for n in other if n.startswith("layer")} == set()
    assert "embed" in other
    assert parts["linattn_scan"] == {"layer%d_delta" % i for i in (0, 1, 3)}
    assert parts["linattn_proj_conv"] >= {
        "layer0_mixer_norm", "layer0_q", "layer0_qconv", "layer0_a",
        "layer0_b", "layer0_gnorm", "layer0_g", "layer0_g_act",
        "layer0_gated", "layer0_o", "layer0_mixer_add", "layer3_kconv_act"}
    assert parts["attention_kernel"] == {"layer2_attn"}
    assert {"layer2_mixer_norm", "layer2_q", "layer2_kv_a", "layer2_kv_norm",
            "layer2_kv_b", "layer2_gate", "layer2_gate_act", "layer2_gated",
            "layer2_o", "layer2_mixer_add"} <= parts["attention_proj"]
    assert parts["dense_ffn"] >= {"layer0_ffn_norm", "layer0_ffn_up",
                                  "layer0_ffn_add"}
    assert parts["moe_grouped_matmul"] == {
        "layer%d_ffn_experts" % i for i in (1, 2, 3)}
    assert {"layer1_ffn_norm", "layer1_ffn_shared_up", "layer1_ffn_sum",
            "layer1_ffn_add"} <= parts["moe_rest"]
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
                 "jit(step)/fwd/FullyConnected:layer2_kv_b/dot_general",
                 "jit(step)/bwd/CausalAttention:layer2_attn/dot_general",
                 "jit(step)/fwd/FullyConnected:layer0_ffn_up/dot_general",
                 "jit(step)/fwd/RoutedExperts:layer1_ffn_experts/while",
                 "jit(step)/bwd/FullyConnected:layer2_ffn_shared_up/dot",
                 "jit(step)/fwd/FullyConnected:lm_head/dot_general",
                 "jit(step)/update/mul"]
        return [(0, [("fusion.%d" % i, p, t + 1e5 * i, 5e4)
                     for i, p in enumerate(paths)])]

    cell = toy_lm_bailing.cell(compute_dtype="float32")
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
    cell, _, line, _ = traced
    want = set(harness.metric_names(cell["spec"], "per_layer",
                                    toy_lm_bailing.CELL))
    assert len(want) == 30 and set(line["metrics"]) == want
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["fit_dispatches_per_step"] == 1.0
    assert m["step_compiles_in_window"] == 0.0 and m["step_jit_entries"] == 1
    assert m["input_h2d_bytes_per_step"] == 0.0
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_traced_run_reads_the_parts_by_scope(traced):
    """One lent event a part: the delta rule's op and its projections each
    under their reader, attention's two parts together, the experts' two
    together; every roofline the cell lists reads something."""
    _, _, line, out = traced
    m = {k: v["value"] for k, v in line["metrics"].items()}
    lent_ms = m["linattn_scan_ms_per_step"]
    assert lent_ms > 0
    for name in ("linattn_proj_conv_ms_per_step", "mla_proj_ms_per_step",
                 "dense_ffn_ms_per_step", "lm_head_loss_ms_per_step"):
        assert m[name] == pytest.approx(lent_ms)
    for name in ("attention_ms_per_step", "moe_experts_ms_per_step"):
        assert m[name] == pytest.approx(2 * lent_ms)
    for name in ("linattn_scan_roofline", "mla_proj_roofline",
                 "attention_roofline", "moe_grouped_matmul_roofline",
                 "lm_step_roofline"):
        assert m[name] > 0
    assert any(l.startswith("roofline linattn_scan") for l in out)


def test_traced_run_counts_lowerings_and_routed_rows(traced):
    """The lowering counters once a traced node, and the experts' rows as
    the device counted them: every pair of every expert layer, none
    dropped."""
    _, _, line, out = traced
    args = toy_lm_bailing.ARGS
    lowered = {l.split()[1]: int(l.split()[3]) for l in out
               if l.startswith("lowering: ")}
    assert lowered == {"lower.delta_rule_gate.channel": 3,
                       "lower.delta_rule_kernel.xla_chunked": 3,
                       "lower.attention_kernel.xla_blockwise": 1,
                       "lower.experts_body.swiglu": 3,
                       "lower.experts_kernel.xla_loop": 3}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["moe_dropped_rows_per_step"] == 0.0
    pairs = 3 * 2 * args["seq_len"] * args["top_k"]
    assert 0 < m["moe_rows_here_per_step"] < pairs
