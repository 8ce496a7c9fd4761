"""The trace reduction: on a hand-made trace whose every number can be
worked out on paper, and on cuts of traces recorded on the v5e."""
import glob
import gzip
import json
import os

import pytest

from benchmark.trace import reduce as R
from benchmark.trace import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6   # ns


def _trace(device_ops, async_ops=(), spans=(), second_device=None):
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [list(e) for e in device_ops]},
        {"name": "Async XLA Ops", "events": [list(e) for e in async_ops]},
        {"name": "Steps", "events": [["0", "", 0.0, 100 * MS]]}]}]
    if second_device is not None:
        planes.append({"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops",
             "events": [list(e) for e in second_device]}]})
    planes.append({"name": "/host:CPU", "lines": [{
        "name": "main", "events": [[R.SPAN_PREFIX + n, "", s, d]
                                   for n, s, d in spans]
        + [["PjRt::Execute", "", 0.0, 5 * MS]]}]})
    planes.append({"name": "/device:CUSTOM:Megascale Trace",
                   "lines": [{"name": "x", "events": [["y", "", 0, 1]]}]})
    return {"planes": planes}


def test_interval_arithmetic():
    assert R.union([(3, 5), (1, 2), (4, 7), (7, 8)]) == [(1, 2), (3, 8)]
    assert R.subtract([(0, 10)], [(1, 2), (3, 5), (9, 12)]) == \
        [(0, 1), (2, 3), (5, 9)]
    assert R.subtract([(0, 4), (6, 9)], [(2, 7)]) == [(0, 2), (7, 9)]
    assert R.clip([(0, 4), (6, 9)], 3, 7) == [(3, 4), (6, 7)]
    assert R.total([(0, 2), (5, 6)]) == 3


def test_busy_union_idle_by_span_and_per_op_sums():
    # window = the spans' extent: 0..100 ms. Two steps.
    ops = [("fusion.1", "fusion:kOutput", 10 * MS, 20 * MS),
           ("fusion.2", "fusion:kLoop", 25 * MS, 10 * MS),   # overlaps .1
           ("convolution_add_fusion.3", "fusion:kOutput", 40 * MS, 10 * MS),
           ("copy.4", "copy", 60 * MS, 5 * MS),
           ("fusion.5", "fusion:kOutput", 70 * MS, 20 * MS),
           ("copy.9", "copy", 150 * MS, 5 * MS)]            # outside
    spans = [("iter.next", 0, 8 * MS), ("fit_loop", 8 * MS, 47 * MS),
             ("iter.next", 55 * MS, 10 * MS), ("fit_loop", 65 * MS, 35 * MS)]
    got = R.reduce(_trace(ops, spans=spans), steps=2)
    assert got["window_s"] == pytest.approx(0.100)
    # busy: [10,35] + [40,50] + [60,65] + [70,90] = 60 ms
    assert got["busy_s"] == pytest.approx(0.060)
    # idle gaps: [0,10] [35,40] [50,60] [65,70] [90,100]
    #   under iter.next: [0,8] + [55,60] = 13 ms
    #   under fit_loop:  [8,10] + [35,40] + [50,55] + [65,70] + [90,100] = 27
    assert got["idle_by_span_s"]["iter.next"] == pytest.approx(0.013)
    assert got["idle_by_span_s"]["fit_loop"] == pytest.approx(0.027)
    assert sum(got["idle_by_span_s"].values()) == pytest.approx(0.040)
    assert got["per_op_s"] == pytest.approx(
        {"fusion:kOutput": 0.040, "fusion:kLoop": 0.010,
         "convolution_add_fusion": 0.010, "copy": 0.005})
    assert got["conv_dot_s"] == pytest.approx(0.050)
    assert got["collective_s"] == 0 and got["collective_exposed_s"] == 0
    top = R.breakdown(got)
    assert top["device_ops"][0] == ["fusion:kOutput", pytest.approx(0.040)]
    assert top["idle_gaps"][0][0] == "fit_loop"


def test_idle_under_a_nested_span_counts_once():
    spans = [("iter.next", 0, 10 * MS), ("input.next", 2 * MS, 4 * MS),
             ("fit_loop", 10 * MS, 10 * MS)]
    ops = [("fusion.1", "fusion:kLoop", 8 * MS, 12 * MS)]
    got = R.reduce(_trace(ops, spans=spans), steps=1)
    assert got["idle_by_span_s"] == pytest.approx(
        {"input.next": 0.004, "iter.next": 0.004})


def test_exposed_and_hidden_collective_time():
    ops = [("fusion.1", "fusion:kOutput", 0, 30 * MS),
           ("all-reduce.2", "all-reduce", 30 * MS, 10 * MS),  # exposed
           ("all-reduce-start.3", "all-reduce-start", 40 * MS, 1 * MS),
           ("fusion.4", "fusion:kLoop", 41 * MS, 19 * MS),
           ("all-reduce-done.3", "all-reduce-done", 60 * MS, 5 * MS)]
    # the async one is in flight 40..65: hidden under fusion.4 for 19 ms
    async_ops = [("all-reduce-start.3", "all-reduce-start", 40 * MS,
                  25 * MS),
                 ("copy-start.8", "copy-start", 0, 50 * MS)]
    spans = [("fit_loop", 0, 80 * MS)]
    got = R.reduce(_trace(ops, async_ops, spans), steps=1)
    assert got["collective_s"] == pytest.approx(0.035)       # 30..65
    assert got["collective_exposed_s"] == pytest.approx(0.016)
    # busy counts the collectives' own events too: 0..41..60..65
    assert got["busy_s"] == pytest.approx(0.065)


def test_busy_is_the_mean_over_the_chips():
    ops0 = [("fusion.1", "fusion:kLoop", 0, 40 * MS)]
    ops1 = [("fusion.1", "fusion:kLoop", 0, 20 * MS)]
    got = R.reduce(_trace(ops0, spans=[("fit_loop", 0, 50 * MS)],
                          second_device=ops1), steps=1)
    assert got["devices"] == 2
    assert got["busy_s"] == pytest.approx(0.030)


def test_a_trace_without_spans_or_device_is_an_error():
    with pytest.raises(RuntimeError):
        R.reduce(_trace([("a.1", "copy", 0, 1)]), steps=1)


@pytest.mark.parametrize("text,want", [
    ("%fusion = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) fusion(u32[2]{0:T(128)"
     "S(1)} %copy-done), kind=kLoop, calls=%fused_computation.1",
     ("fusion", "fusion:kLoop")),
    ("%copy-done = u32[2]{0:T(128)S(1)} copy-done((u32[2]{0:T(128)S(1)}, "
     "u32[2]{0:T(128)}, u32[]{:S(2)}) %copy-start)",
     ("copy-done", "copy-done")),
    ("%convert_reduce_fusion.1 = (f32[192]{0:T(256)S(1)}, f32[192]{0:T(256)"
     "S(1)}, bf16[256,192,55,55]{0,1,3,2:T(8,128)(2,1)}) fusion(f32[192,64,"
     "3,3]{0,1,3,2} %p), kind=kOutput, calls=%fused_computation.5",
     ("convert_reduce_fusion.1", "fusion:kOutput")),
    ("%select_and_scatter.45 = bf16[256,1024,7,7]{0,1,3,2:T(8,128)(2,1)S(1)}"
     " select-and-scatter(bf16[256,1024,7,7]{0,1,3,2} %a, bf16[] %b)",
     ("select_and_scatter.45", "select-and-scatter")),
    ("%all-reduce-start.3 = (f32[64]{0}, f32[64]{0}) all-reduce-start("
     "f32[64]{0} %g), replica_groups={{0,1,2,3}}",
     ("all-reduce-start.3", "all-reduce-start")),
    ("jit_step(2745305033701948256)", ("jit_step(2745305033701948256)", "")),
])
def test_parse_hlo(text, want):
    assert xplane.parse_hlo(text) == want


RECORDED = sorted(glob.glob(os.path.join(HERE, "data", "*.trace.json.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_trace(path):
    """Cuts of real v5e traces (``tools/record_trace.py``, PR 23): the
    reduction finds the harness's spans and the device's operations in
    the profiler's own naming, and its parts add up."""
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    with open(path.replace(".trace.json.gz", ".expect.json")) as f:
        expect = json.load(f)
    got = R.reduce(trace, steps=expect["steps"])
    assert got["devices"] == expect["devices"]
    idle = got["window_s"] - R.total(R.clip(R.union(
        [(s, s + d) for _, _, s, d in
         R.device_lines(trace)[0]["XLA Ops"]]),
        *_window(trace))) / 1e9
    assert sum(got["idle_by_span_s"].values()) == pytest.approx(idle)
    assert 0 < got["busy_s"] <= got["window_s"]
    assert 0 < got["conv_dot_s"] <= sum(got["per_op_s"].values())
    assert got["collective_exposed_s"] <= got["collective_s"]
    for key, value in expect["values"].items():
        assert got[key] == pytest.approx(value, rel=1e-6), key
    assert (got["collective_s"] > 0) == (expect["devices"] > 1)


def _window(trace):
    spans = R.host_spans(trace)
    return min(s for _, s, _ in spans), max(e for _, _, e in spans)
