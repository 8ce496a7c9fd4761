"""CPU rehearsals of the ``fit_lm_ref`` driver at toy width
(``toy_lm_ref.py``): a sound run is ``correct``; a switched-off update and
a halved learning rate are not; the reference's controls read above the
stated precision's floor; a traced run reports every per-layer metric the
cell lists, the new readers among them; a program without the factory
exits at once."""
import gzip
import json
import os
import time

import pytest

import toy_lm_ref
from benchmark import harness
from benchmark.drivers import fit_lm_ref
from benchmark.reference import olmo_hybrid as ref
from benchmark.trace import scopes
from test_fit_lm import _failed, on_cpu  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))


def _run(cell, capsys, seed=3000000019, seconds=0.5, trace=False,
         controls=()):
    res = fit_lm_ref.run(cell, seed=seed, seconds=seconds, trace=trace,
                         t_start=time.perf_counter() - 1000.0,
                         controls=controls)
    out = capsys.readouterr().out.strip().splitlines()
    return res, json.loads(out[-1]), out


def test_sound_run_is_correct_and_sits_on_the_reference(on_cpu, capsys):
    res, line, out = _run(toy_lm_ref.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    got = {r[0]: r[1] for r in res["rows"]}
    assert got.pop("step1_excess_noise") == pytest.approx(-1.0, abs=1e-3)
    assert got.pop("window_loss_over_first_loss") < 1.0
    assert all(v < 2e-3 for v in got.values()), res["rows"]
    assert any("tokens/s" in l for l in out)
    # every parameter of the model is a leaf that was compared
    assert set(res["want"]["delta_norms"]) == set(ref.param_shapes(
        toy_lm_ref.ARGS))


def test_a_switched_off_update_is_not_correct(on_cpu, capsys, monkeypatch):
    from mxnet_tpu import optimizer

    monkeypatch.setattr(optimizer, "_update_math",
                        lambda kind, n, clipped: (
                            lambda w, g, states, s: (w, states)))
    res, line, _ = _run(toy_lm_ref.cell(compute_dtype="float32"), capsys)
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
    res, line, _ = _run(toy_lm_ref.cell(compute_dtype="float32"), capsys)
    assert line["correct"] is False
    assert "delta_norm_median_leaf_gap" in _failed(res["rows"])
    assert "grad_norm_median_leaf_gap" not in _failed(res["rows"])


def test_an_undoubled_beta_underneath_is_not_correct(on_cpu, capsys):
    """The program built with ``b`` left in (0, 1) underneath the
    reference that doubles it: another model, and the first step's
    log-probabilities already say so."""
    cell = toy_lm_ref.cell(compute_dtype="float32")
    cell["config"]["model"]["args"]["neg_eigval"] = False
    res, line, _ = _run(cell, capsys)
    assert line["correct"] is False
    assert "step1_excess_noise" in _failed(res["rows"])


def test_the_controls_read_above_the_stated_precisions_floor(on_cpu, capsys):
    """The reference in the program's place, the bfloat16 pipeline with one
    thing wrong, on the run's own weights and batch: each reads far above
    a sound bfloat16 program (which reads about 0). (At this width a
    bfloat16 run's first gradients swing by a third, in the reference's own
    bfloat16 pipeline as in the program: only the precision row is read.)"""
    controls = ("int8_matmul", "b_undoubled", "no_l2norm", "bf16_state")
    res, line, out = _run(toy_lm_ref.cell(), capsys, controls=controls)
    sound = {r[0]: r[1] for r in res["rows"]}["step1_excess_noise"]
    assert sound < 1.0
    reads = res["controls"]
    assert set(reads) == set(controls)
    for name in ("int8_matmul", "b_undoubled", "no_l2norm"):
        assert not reads[name] <= 1.0, (name, reads[name])
    lines = [l for l in out if l.startswith("control ")]
    assert len(lines) == 4
    assert sum("fails, as it must" in l for l in lines) >= 3


def test_a_program_without_the_factory_exits_at_once(on_cpu, capsys):
    cell = toy_lm_ref.cell()
    cell["config"]["model"]["factory"] = "mxnet_tpu.models.get_no_such_model"
    t0 = time.perf_counter()
    with pytest.raises(SystemExit, match="the program has no "
                       "mxnet_tpu.models.get_no_such_model"):
        fit_lm_ref.run(cell, seed=1, seconds=0.5, trace=False,
                       t_start=time.perf_counter())
    assert time.perf_counter() - t0 < 30.0


def test_traced_run_reports_every_per_layer_metric(on_cpu, capsys,
                                                   monkeypatch):
    """``--trace 1`` on the CPU, the reduction lent one device event and
    the scope reader a scope for each part (the numbers mean nothing
    here): every per-layer metric the cell lists is on the line."""
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
        paths = ["jit(step)/fwd/GatedDeltaRule:layer0_delta/while",
                 "jit(step)/bwd/FullyConnected:layer1_q/dot_general",
                 "jit(step)/fwd/FullyConnected:layer2_ffn_up/dot_general",
                 "jit(step)/bwd/CausalAttention:layer3_attn/dot_general",
                 "jit(step)/fwd/RMSNorm:layer3_qnorm/mul",
                 "jit(step)/fwd/FullyConnected:lm_head/dot_general",
                 "jit(step)/update/mul"]
        return [(0, [("fusion.%d" % i, p, t + 1e5 * i, 5e4)
                     for i, p in enumerate(paths)])]

    monkeypatch.setattr(R, "reduce", with_a_device_plane)
    monkeypatch.setattr(scopes, "load", fake_scopes)
    cell = toy_lm_ref.cell(compute_dtype="float32")
    res, line, out = _run(cell, capsys, seed=7, seconds=3.0, trace=True)
    want = set(harness.metric_names(cell["spec"], "per_layer",
                                    toy_lm_ref.CELL))
    assert len(want) == 24 and set(line["metrics"]) == want
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["fit_dispatches_per_step"] == 1.0
    assert m["step_compiles_in_window"] == 0.0 and m["step_jit_entries"] == 1
    assert m["input_h2d_bytes_per_step"] == 0.0
    # one lent event a part, two under attention (kernel and norm)
    lent_ms = m["linattn_scan_ms_per_step"]
    assert lent_ms > 0
    for name in ("linattn_proj_conv_ms_per_step", "dense_ffn_ms_per_step",
                 "lm_head_loss_ms_per_step"):
        assert m[name] == pytest.approx(lent_ms)
    assert m["attention_ms_per_step"] == pytest.approx(2 * lent_ms)
    assert m["linattn_scan_roofline"] > 0 and m["lm_step_roofline"] > 0
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert any(l.startswith("lowering: lower.delta_rule_kernel.xla_chunked")
               for l in out)
    assert any(l.startswith("roofline linattn_scan") for l in out)


def test_the_new_readers_find_nothing_in_a_program_without_the_parts():
    """On a trace whose scopes name none of this model's parts (the parent,
    another model) the new readers return nothing and do not raise."""
    trace = {"steps": 4, "busy_s": 1.0,
             "scope_s": {"ssm_scan": 0.1, "optimizer": 0.2}}
    cell = {"step_parts": {"ssm_scan": (1e9, 1e6)},
            "peaks": {"flops_per_s": {"bfloat16": 1e12},
                      "hbm_bytes_per_s": 1e11},
            "config": {"compute_dtype": "bfloat16"}}
    for name, empty in (("linattn_scan_ms_per_step", 0.0),
                        ("linattn_proj_conv_ms_per_step", 0.0),
                        ("dense_ffn_ms_per_step", 0.0),
                        ("linattn_scan_roofline", None)):
        mod = harness.importlib.import_module("benchmark.metrics." + name)
        assert mod.read(trace, {}, [], cell) == empty
        assert mod.read({"steps": 4}, {}, [], {}) is None


def test_the_new_readers_on_a_recorded_cut_of_the_cells_trace():
    """Steps of ``olmo_hybrid_fit_packed8k`` on the v5e, cut by
    ``tools/record_scopes_ref.py``: device time by part as the run printed
    it, every part of the model there."""
    path = os.path.join(HERE, "data", toy_lm_ref.CELL)
    if not os.path.exists(path + ".scopes.json.gz"):
        pytest.skip("no recorded cut yet")
    with gzip.open(path + ".scopes.json.gz", "rt") as f:
        cut = json.load(f)
    with open(path + ".scopes.expect.json") as f:
        expect = json.load(f)
    got = scopes.by_part([tuple(e) for e in cut["events"]], cut["lo"],
                         cut["hi"], ref.part_of(expect["args"]))
    assert set(got) == set(expect["by_part_s"])
    for part, seconds in expect["by_part_s"].items():
        assert got[part] == pytest.approx(seconds, rel=1e-6)
    trace = {"steps": 1, "scope_s": got}
    for name, parts in (("linattn_scan_ms_per_step", ("linattn_scan",)),
                        ("linattn_proj_conv_ms_per_step",
                         ("linattn_proj_conv",)),
                        ("dense_ffn_ms_per_step", ("dense_ffn",))):
        mod = harness.importlib.import_module("benchmark.metrics." + name)
        want = 1e3 * sum(got[p] for p in parts)
        assert want > 0 and mod.read(trace, {}, [], {}) == pytest.approx(want)
    for must in ("attention_proj", "attention_kernel", "lm_head_loss",
                 "optimizer"):
        assert got[must] > 0
