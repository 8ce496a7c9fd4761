"""CPU rehearsals of the ``fit_lm_ref`` driver over the ``glm4_moe_lite``
reference at toy width (``toy_lm_glm.py``): a sound run is ``correct`` and
starts from balanced experts; a switched-off update, a halved learning rate
and an ungated expert underneath are not; the reference's controls read
above the stated precision's floor; ONE traced run, shared by the cases that
only read it, reports every per-layer metric the cell lists, the two new
readers among them, with the lowerings and the experts' counters."""
import contextlib
import io
import json
import time

import numpy as np
import pytest

import toy_lm_glm
from benchmark import harness
from benchmark.drivers import fit_lm_ref
from benchmark.reference import glm4_moe_lite as ref
from benchmark.trace import scopes
from test_fit_lm import _failed, on_cpu  # noqa: F401

CONTROLS = ("int8_matmul", "rope_whole_head", "no_latent_norm",
            "experts_ungated", "weights_unnormalised")


def _run(cell, capsys, seed=3000000019, seconds=0.5, controls=()):
    res = fit_lm_ref.run(cell, seed=seed, seconds=seconds, trace=False,
                         t_start=time.perf_counter() - 1000.0,
                         controls=controls)
    out = capsys.readouterr().out.strip().splitlines()
    return res, json.loads(out[-1]), out


def test_sound_run_is_correct_and_sits_on_the_reference(on_cpu, capsys):
    res, line, out = _run(toy_lm_glm.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    got = {r[0]: r[1] for r in res["rows"]}
    assert got.pop("step1_excess_noise") == pytest.approx(-1.0, abs=1e-3)
    assert got.pop("window_loss_over_first_loss") < 1.0
    assert all(v < 2e-3 for v in got.values()), res["rows"]
    # every parameter is a leaf that was compared (each held expert's slice
    # of the stacked up and down weights its own), and the selection biases,
    # which the step moves itself, among the leaves of the change
    shapes = ref.param_shapes(toy_lm_glm.ARGS)
    want = set(ref.leaves({k: np.zeros(s) for k, s in shapes.items()}))
    assert set(res["want"]["delta_norms"]) == want
    states = {k for k in want if k.endswith(ref.STATE)}
    assert len(states) == 2
    assert set(res["want"]["grad_norms"]) == want - states
    assert all(res["got"]["delta_norms"][k] > 0 for k in states)
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
    res, line, _ = _run(toy_lm_glm.cell(compute_dtype="float32"), capsys)
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
    res, line, _ = _run(toy_lm_glm.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert "delta_norm_median_leaf_gap" in _failed(res["rows"])
    assert "grad_norm_median_leaf_gap" not in _failed(res["rows"])


def test_an_ungated_expert_underneath_is_not_correct(on_cpu, capsys,
                                                     monkeypatch):
    """The program's routed experts with the gate dropped (``W_down W_up
    x``) underneath the reference that gates: another model, and the first
    step's log-probabilities already say so."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import moe

    def ungated(xb, w_gate_e, w_up_e, cd):
        u = jnp.dot(xb, w_up_e, preferred_element_type=jnp.float32)
        return jnp.zeros_like(u), u, u.astype(cd)

    monkeypatch.setattr(moe, "_swiglu_block", ungated)
    res, line, _ = _run(toy_lm_glm.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert "step1_excess_noise" in _failed(res["rows"])


def test_the_controls_read_above_the_stated_precisions_floor(on_cpu, capsys):
    """The reference in the program's place, the bfloat16 pipeline with one
    thing wrong, on the run's own weights and batch: each reads far above a
    sound bfloat16 program (which reads about 0)."""
    res, line, out = _run(toy_lm_glm.cell(), capsys, controls=CONTROLS)
    sound = {r[0]: r[1] for r in res["rows"]}["step1_excess_noise"]
    assert sound < 1.0
    reads = res["controls"]
    assert set(reads) == set(CONTROLS)
    for name in CONTROLS:
        assert not reads[name] <= 1.0, (name, reads[name])
    lines = [l for l in out if l.startswith("control ")]
    assert len(lines) == len(CONTROLS)
    assert all("fails, as it must" in l for l in lines)


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
        paths = ["jit(step)/fwd/FullyConnected:layer0_kv_b/dot_general",
                 "jit(step)/bwd/CausalAttention:layer1_attn/dot_general",
                 "jit(step)/fwd/FullyConnected:layer0_ffn_up/dot_general",
                 "jit(step)/fwd/RoutedExperts:layer1_ffn_experts/while",
                 "jit(step)/bwd/FullyConnected:layer2_ffn_shared_up/dot",
                 "jit(step)/fwd/FullyConnected:lm_head/dot_general",
                 "jit(step)/update/mul"]
        return [(0, [("fusion.%d" % i, p, t + 1e5 * i, 5e4)
                     for i, p in enumerate(paths)])]

    cell = toy_lm_glm.cell(compute_dtype="float32")
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
                                    toy_lm_glm.CELL))
    assert len(want) == 27 and set(line["metrics"]) == want
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["fit_dispatches_per_step"] == 1.0
    assert m["step_compiles_in_window"] == 0.0 and m["step_jit_entries"] == 1
    assert m["input_h2d_bytes_per_step"] == 0.0
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_traced_run_reads_the_parts_by_scope(traced):
    """One lent event a part: the projection chains under the new reader,
    attention's two parts together, the experts' two together."""
    _, _, line, out = traced
    m = {k: v["value"] for k, v in line["metrics"].items()}
    lent_ms = m["mla_proj_ms_per_step"]
    assert lent_ms > 0
    for name in ("dense_ffn_ms_per_step", "lm_head_loss_ms_per_step"):
        assert m[name] == pytest.approx(lent_ms)
    for name in ("attention_ms_per_step", "moe_experts_ms_per_step"):
        assert m[name] == pytest.approx(2 * lent_ms)
    for name in ("mla_proj_roofline", "attention_roofline",
                 "moe_grouped_matmul_roofline", "lm_step_roofline"):
        assert m[name] > 0
    assert any(l.startswith("roofline attention_proj") for l in out)


def test_traced_run_counts_lowerings_and_routed_rows(traced):
    """The lowering counters once a traced node, and the experts' rows as
    the device counted them: every pair of every expert layer, none
    dropped."""
    _, _, line, out = traced
    args = toy_lm_glm.ARGS
    lowered = {l.split()[1]: int(l.split()[3]) for l in out
               if l.startswith("lowering: ")}
    assert lowered == {"lower.attention_kernel.xla_blockwise": args["layers"],
                       "lower.experts_body.swiglu": 2}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["moe_dropped_rows_per_step"] == 0.0
    pairs = 2 * 2 * args["seq_len"] * args["top_k"]
    assert 0 < m["moe_rows_here_per_step"] < pairs


def test_the_new_readers_find_nothing_in_a_program_without_the_parts():
    """On a trace whose scopes name none of this model's parts (the parent,
    another model) the new readers return nothing and do not raise."""
    trace = {"steps": 4, "busy_s": 1.0,
             "scope_s": {"ssm_scan": 0.1, "optimizer": 0.2}}
    cell = {"step_parts": {"ssm_scan": (1e9, 1e6)},
            "peaks": {"flops_per_s": {"bfloat16": 1e12},
                      "hbm_bytes_per_s": 1e11},
            "config": {"compute_dtype": "bfloat16"}}
    for name, empty in (("mla_proj_ms_per_step", 0.0),
                        ("mla_proj_roofline", None)):
        mod = harness.importlib.import_module("benchmark.metrics." + name)
        assert mod.read(trace, {}, [], cell) == empty
        assert mod.read({"steps": 4}, {}, [], {}) is None
