"""The readers of the build record (``setup_step_backend_builds``,
``setup_step_build_s``): each on faked gauges, on a program that publishes
none of them (the parent of the PR that added them: nothing to read,
nothing raised), and after a fused ``fit`` on the CPU, where both return a
number: the persistent cache is off there, so the step was built."""
import os

import numpy as np
import pytest

from benchmark import harness

READERS = ["setup_step_backend_builds", "setup_step_build_s"]


def _reader(name):
    """The reader as the harness loads and calls it."""
    spec = {"per_layer": [{"name": name, "unit": "x"}]}
    return harness.read_per_layer(spec, "cell", {}, {"steps": 4}, [],
                                  {}).get(name, {}).get("value")


@pytest.fixture
def tel():
    from mxnet_tpu import telemetry

    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.reset()
    telemetry.disable()


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_record_gives_no_metric(name, tel):
    tel.set_gauge("step.fused_jit_entries", 1)   # others' are there
    tel.inc("step.fused_steps", 40)
    tel.disable()
    assert _reader(name) is None


@pytest.mark.parametrize("cache_read, builds", [(1, 0), (0, 1)])
def test_build_readers_on_faked_gauges(tel, cache_read, builds):
    tel.set_gauge("compile.fused_step.cache_read", cache_read)
    tel.set_gauge("compile.fused_step.build_s", 12.5)
    tel.set_gauge("compile.metric.fold.cache_read", 1 - cache_read)
    tel.set_gauge("compile.metric.fold.build_s", 0.25)   # another site's
    tel.disable()
    assert _reader("setup_step_backend_builds") == builds
    assert _reader("setup_step_build_s") == 12.5


def test_the_new_entries_list_the_image_cells_and_only_append():
    spec = harness._load(os.path.join(harness.ROOT, "BENCHMARK.json"))
    tail = spec["per_layer"][-len(READERS):]
    assert [m["name"] for m in tail] == READERS
    for m in tail:
        assert m["workloads"] == ["inception_bn_fit_resident",
                                  "resnet50_fit_resident"]
        assert m["source"] == "program_counter"
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))
        assert (m["layer"], m["moves"]) == ("Device runtime", "setup_s")


def test_readers_after_a_fused_fit_on_the_cpu(tel, monkeypatch):
    import mxnet_tpu as mx
    from mxnet_tpu import xprof

    monkeypatch.setattr(xprof, "_override", None)
    monkeypatch.setenv("MXNET_TPU_FUSED_STEP", "1")
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=5, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    steps, batch = 6, 4
    x = np.random.RandomState(0).rand(steps * batch, 8).astype(np.float32)
    y = (np.arange(steps * batch) % 5).astype(np.float32)
    mx.mod.Module(net).fit(
        mx.io.NDArrayIter(x, y, batch_size=batch), num_epoch=1,
        optimizer="sgd", eval_metric="ce",
        optimizer_params={"learning_rate": 0.1})
    tel.disable()
    assert tel.peek("step.fused_steps") == steps
    assert _reader("setup_step_backend_builds") == 1    # the cache is off
    assert _reader("setup_step_build_s") > 0
