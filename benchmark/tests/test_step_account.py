"""The readers of the compiled step's own account: each on a faked set of
the program's gauges (``compile.fused_step.*``, its census, ``device.
hbm_*``), and on a program that publishes none of them: the parent of the
PR that added the gauges, where every reader finds nothing to read and
raises nothing."""
import pytest

from benchmark import harness

READERS = ["step_program_temp_gb", "step_program_held_gb",
           "step_hbm_at_fence_gb", "step_update_fused_ops",
           "step_update_min_ms", "step_recompute_flops_pct"]
CELL = {"peaks": {"flops_per_s": {"bfloat16": 2e14},
                  "hbm_bytes_per_s": 8e11},
        "config": {"compute_dtype": "bfloat16"}}
CENSUS = {"fwd": (40, 6e12, 4e9), "recompute": (10, 1e12, 1e9),
          "bwd": (50, 9e12, 6e9), "recompute+bwd": (5, 1e12, 1e9),
          "bwd+update": (12, 4e12, 1.6e10), "update": (30, 0, 8e9),
          "none": (25, 0, 2e9)}


def _reader(name):
    """The reader as the harness loads and calls it."""
    spec = {"per_layer": [{"name": name, "unit": "x"}]}
    return harness.read_per_layer(spec, "cell", {}, {"steps": 4}, [],
                                  CELL).get(name, {}).get("value")


@pytest.fixture
def gauges():
    from mxnet_tpu import telemetry

    telemetry.reset()
    telemetry.enable()
    yield telemetry.set_gauge
    # the drivers switch telemetry off before the readers run: the
    # gauges keep their values
    telemetry.reset()
    telemetry.disable()


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_gauges_gives_no_metric(name, gauges):
    from mxnet_tpu import telemetry

    gauges("step.fused_jit_entries", 1)     # others' gauges are there
    telemetry.disable()
    assert _reader(name) is None


def test_memory_readers_on_faked_gauges(gauges):
    from mxnet_tpu import telemetry

    gauges("compile.fused_step.temp_bytes", 5.5e9)
    gauges("compile.fused_step.held_bytes", 15.25e9)
    gauges("compile.metric.fold.held_bytes", 4096)   # another site's
    gauges("device.hbm_in_use_bytes", 9.5e9)
    telemetry.disable()
    assert _reader("step_program_temp_gb") == pytest.approx(5.5)
    assert _reader("step_program_held_gb") == pytest.approx(15.25)
    # in use without the reserve of the same moment is no footprint
    assert _reader("step_hbm_at_fence_gb") is None
    telemetry.enable()
    gauges("device.hbm_reserved_bytes", 5.25e9)
    telemetry.disable()
    assert _reader("step_hbm_at_fence_gb") == pytest.approx(14.75)


def test_census_readers_on_a_faked_census(gauges, capsys):
    from mxnet_tpu import telemetry

    for name, (ops, flops, nbytes) in CENSUS.items():
        base = "compile.fused_step.census.%s." % name
        gauges(base + "ops", ops)
        gauges(base + "flops", flops)
        gauges(base + "bytes", nbytes)
    for phase, flops in (("fwd", 6e12), ("recompute", 1.5e12),
                         ("bwd", 13.5e12)):
        gauges("compile.fused_step.matrix_flops." + phase, flops)
    telemetry.disable()
    assert _reader("step_update_fused_ops") == 12
    # bwd+update: 20 ms of bytes over 20 ms of FLOPs; update: 10 ms
    assert _reader("step_update_min_ms") == pytest.approx(20.0 + 10.0)
    out = capsys.readouterr().out
    assert out.count("census ") == len(CENSUS)
    assert "census bwd+update: 12 ops" in out
    # by the products' own phase, not by the sets that hold one
    assert _reader("step_recompute_flops_pct") == pytest.approx(
        100.0 * 1.5e12 / 21e12)


def test_no_update_fused_and_nothing_recomputed_read_zero(gauges):
    from mxnet_tpu import telemetry

    for name in ("fwd", "bwd", "update"):
        base = "compile.fused_step.census.%s." % name
        gauges(base + "ops", 3)
        gauges(base + "flops", 1e9)
        gauges(base + "bytes", 1e6)
    gauges("compile.fused_step.matrix_flops.fwd", 1e9)
    gauges("compile.fused_step.matrix_flops.bwd", 2e9)
    telemetry.disable()
    assert _reader("step_update_fused_ops") == 0
    assert _reader("step_recompute_flops_pct") == 0
