"""The device runtime's contract on a host that may hold two backends:
nothing falls back to the CPU behind a ``tpu`` label, importing the package
takes no chip, the compile cache can be placed from outside, kernels choose
interpreter vs Mosaic per lowering, and generated files are rebuilt rather
than trusted."""
import json
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import metric, telemetry, xprof
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import pallas_kernels as pk

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_REPORT = (
    "import json, sys\n"
    "sys.path.insert(0, %r)\n"
    "import mxnet_tpu, jax\n"
    "from jax._src import xla_bridge\n"
    "print(json.dumps({'dir': jax.config.jax_compilation_cache_dir,\n"
    "                  'backends': sorted(xla_bridge._backends)}))\n" % REPO)


def test_import_places_the_compile_cache_and_takes_no_chip(tmp_path):
    """A fresh interpreter, started in another directory, with
    JAX_PLATFORMS unset — on a TPU host that is the real default
    configuration: the cache points at a fixed path beside the package
    (no pid, time or temp name: it equals a constant, so it is the same
    in every process) and importing initialised no backend."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                        "PYTHONPATH")}
    r = subprocess.run([sys.executable, "-c", _REPORT], cwd=str(tmp_path),
                       capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    rep = json.loads(r.stdout.splitlines()[-1])
    assert rep["dir"] == os.path.join(REPO, ".jax_cache")
    assert rep["backends"] == [], "import initialised %s" % rep


def test_compile_cache_is_left_alone_when_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it (at import) and the
    package sets nothing. A CPU-pinned process gets no cache at all."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    mx._place_compile_cache()
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    mx._place_compile_cache()
    assert calls == []
    # pinned in code rather than by the environment (as conftest does)
    monkeypatch.delenv("JAX_PLATFORMS")
    assert jax.config.jax_platforms == "cpu"
    mx._place_compile_cache()
    assert calls == []


def test_tpu_context_raises_without_an_accelerator():
    with pytest.raises(MXNetError, match="no accelerator.*'cpu'"):
        mx.tpu(0).jax_device()
    with pytest.raises(MXNetError, match="no accelerator"):
        mx.nd.zeros((2, 2), ctx=mx.gpu(0))
    assert mx.cpu(0).jax_device().platform == "cpu"
    assert mx.num_devices("tpu") == 0


def test_metric_colocation_compares_devices_not_ids():
    """cpu:0 and tpu:0 share id 0; labels from a host iterator must not
    look colocated with predictions on the chip."""
    class Dev:
        def __init__(self, platform):
            self.platform, self.id = platform, 0

    class Arr:
        def __init__(self, dev):
            self.sharding = type("S", (), {"device_set": {dev}})()

    host, chip = Dev("cpu"), Dev("tpu")
    assert metric._device_set(Arr(host)) != metric._device_set(Arr(chip))
    assert metric._device_set(Arr(chip)) == metric._device_set(Arr(chip))
    assert metric._device_set(np.zeros(3)) is None


def _lowered_text(fn, args, platform):
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=(platform,)).as_text()


def _kernel_case(name):
    """``(fn, args)`` of one shipped kernel at the smallest shapes its
    ``*_applicable`` rule admits (float32 ones; a case that the rule
    stops admitting fails here, not on the chip)."""
    f32 = jnp.dtype("float32")

    def ones(*shape):
        return jnp.ones(shape, f32)

    if name == "rtc":
        def body(x_ref, o_ref):
            o_ref[:] = x_ref[:] * 2.0
        return (lambda x: pk.pallas_call(
            body, x, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype)),
            (ones(128, 128),))
    if name.startswith("ssd_chunk"):
        # two 64-wide heads share a 128-lane unit; one group, one chunk
        dims, chunk = (2, 64, 1, 128), 128
        assert pk.ssd_chunk_applicable(dims, chunk, f32)
        assert not pk.ssd_chunk_applicable((1, 64, 1, 128), chunk, f32)
        xbc, dt = ones(1, chunk, 2 * 64 + 2 * 128), ones(1, chunk, 2) * 0.1
        a_head, d_skip = -ones(2), ones(2)
        if name == "ssd_chunk_forward":
            return (lambda *a: pk.ssd_chunk_forward(
                *a, dims=dims, chunk=chunk, with_states=True),
                (xbc, dt, a_head, d_skip))
        starts = jnp.zeros((1, 1, 1, 128, 128), f32)
        return (lambda *a: pk.ssd_chunk_backward(*a, dims=dims, chunk=chunk),
                (xbc, dt, a_head, d_skip, starts, ones(1, chunk, 128)))
    if name.startswith("delta_chunk"):
        channel = name.endswith("channel")
        # a decay a head: 8 keys, 8 values, a chunk of one sublane tile; a
        # decay a channel: 128 keys, a chunk of one 16-position sub-chunk
        dk, dv, chunk = (128, 8, 16) if channel else (8, 8, 8)
        rule = pk.delta_channel_applicable if channel \
            else pk.delta_chunk_applicable
        assert rule((1, dk, dv), chunk, f32)
        assert not rule((1, dk, dv), chunk // 2, f32)
        q = ones(1, chunk, 1, dk) * dk ** -0.5
        g = -ones(1, chunk, 1, dk) * 0.1 if channel \
            else -ones(1, chunk, 1) * 0.1
        args = (q, q, ones(1, chunk, 1, dv), g, ones(1, chunk, 1) * 0.5)
        if "forward" in name:
            return (lambda *a: pk.delta_chunk_forward(
                *a, chunk=chunk, with_states=True), args)
        return (lambda *a: pk.delta_chunk_backward(*a, chunk=chunk),
                args + (jnp.zeros((1, 1, 1, dk, dv), f32),
                        ones(1, chunk, 1, dv)))
    if name.startswith("delta_rows"):
        channel = name.endswith("channel")
        # the row-major entry: one head of 128 keys and values, a chunk of
        # one bfloat16 sublane tile, the rows as the projections leave them
        dk, dv, chunk = 128, 128, 16
        assert pk.delta_rows_applicable((1, dk, dv), 1, chunk, chunk, channel)
        assert not pk.delta_rows_applicable((1, dk, dv), 1, chunk, chunk + 8,
                                            channel)
        spec = pk.DeltaRows(chunk, 1, 1, dk, dv, chunk,
                            -5.0 if channel else 0.0, 1e-6)
        rows = jnp.ones((chunk, dk), jnp.bfloat16)
        gate = (rows, ones(1, chunk, 1) * 0.5, ones(1, dk), -ones(1, dk)) \
            if channel else (-ones(1, chunk, 1) * 0.1, ones(1, chunk, 1) * 0.5)
        fill = (None,) * (4 - len(gate))
        if "forward" in name:
            return (lambda *a: pk.delta_rows_forward(
                *a, *fill, spec=spec, with_states=True),
                (rows, rows, rows) + gate)
        return (lambda q, k, v, *a: pk.delta_rows_backward(
            q, k, v, *a[:-2], *fill, *a[-2:], spec=spec),
            (rows, rows, rows) + gate
            + (jnp.zeros((1, 1, 1, dk, dv), f32), rows))
    if name.startswith("grouped_experts"):
        # one expert of 128 -> 8 -> 128 over one block of 8 slots
        gated = name.endswith("gated")
        h, f, block = 128, 8, 8
        assert pk.grouped_experts_applicable(h, f, block, f32, gated, block)
        assert not pk.grouped_experts_applicable(h, f, block // 2, f32,
                                                 gated, block)
        ws = (ones(1, h, f) * 0.1,) * (2 if gated else 1) \
            + (ones(1, f, h) * 0.1,)
        layout = (jnp.arange(block, dtype=jnp.int32), ones(block),
                  jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32))
        if "forward" in name:
            return (lambda x, *ws: pk.grouped_experts_forward(
                x, ws, *layout, gated=gated), (ones(block, h),) + ws)
        return (lambda x, dy, *ws: pk.grouped_experts_backward(
            x, ws, *layout, dy, gated=gated),
            (ones(block, h), ones(block, h)) + ws)
    if name == "attention_backward":
        # a group of two 64-wide heads over one block of 128 positions
        t, d, group = 128, 64, 2
        assert pk.attention_applicable(t, d, d, f32)
        assert not pk.attention_applicable(t + 64, d, d, f32)
        return (pk.attention_backward,
                (ones(1, 1, group, t, d) * 0.1, ones(1, 1, t, d),
                 ones(1, 1, t, d), ones(1, 1, group, t, d),
                 ones(1, 1, group, t) * 4.0, ones(1, 1, group, t)))
    assert name.startswith("attention_relayout")
    from mxnet_tpu.ops import attention

    # two 64-wide heads, one 128-lane tile, turned whole; ``back``: the
    # way of the kernel's result and of every cotangent
    t, heads, d = 128, 2, 64
    tables = tuple(jnp.asarray(a) for a in
                   attention.relayout_tables(t, 1e4, d // 2, d))
    back = name.endswith("back")
    return (lambda x: pk.attention_relayout(
        x, tables, batch=1, heads=heads, half=d // 2, scale=0.125,
        back=back),
        (ones(1, heads, t, d) if back else ones(t, heads * d),))


@pytest.mark.parametrize("name", [
    "rtc", "ssd_chunk_forward", "ssd_chunk_backward", "delta_chunk_forward",
    "delta_chunk_forward_channel", "delta_chunk_backward",
    "delta_chunk_backward_channel", "delta_rows_forward",
    "delta_rows_forward_channel", "delta_rows_backward",
    "delta_rows_backward_channel", "grouped_experts_forward",
    "grouped_experts_forward_gated", "grouped_experts_backward",
    "attention_relayout", "attention_relayout_back", "attention_backward"])
def test_kernels_lower_to_mosaic_for_tpu_and_the_interpreter_for_cpu(name):
    """The interpret decision is taken per lowering: the same traced call
    becomes a Mosaic custom call when lowered for a TPU and interpreter
    HLO when lowered for the CPU — no process-wide answer to flip. A case
    a kernel the train path ships (the five families a cell has timed and
    ``rtc``'s entry), at the smallest shapes its rule admits."""
    fn, args = _kernel_case(name)
    tpu = _lowered_text(fn, args, "tpu")
    cpu = _lowered_text(fn, args, "cpu")
    assert "tpu_custom_call" in tpu
    assert "tpu_custom_call" not in cpu
    # and the CPU lowering really computes
    for out in jax.tree_util.tree_leaves(jax.jit(fn)(*args)):
        assert np.isfinite(np.asarray(out, np.float32)).all()


def test_native_library_older_than_its_sources_is_rebuilt(caplog,
                                                          monkeypatch):
    import mxnet_tpu._native_lib as nl

    if nl.get_lib() is None:
        pytest.skip("no compiler / native library on this host")
    srcs = nl._sources()
    assert srcs and not nl._stale(srcs)
    os.utime(nl._LIB_PATH, (1, 1))          # older than any checkout
    assert nl._stale(srcs)
    monkeypatch.setattr(nl, "_lib", None)
    monkeypatch.setattr(nl, "_tried", False)
    assert nl.get_lib() is not None
    assert not nl._stale(srcs), "get_lib() loaded the stale library"

    # a failed build is said once, at warning level, with the reason
    monkeypatch.setattr(nl, "_lib", None)
    monkeypatch.setattr(nl, "_tried", False)
    monkeypatch.setattr(nl, "_stale", lambda srcs: True)
    monkeypatch.setattr(
        nl.subprocess, "run",
        lambda *a, **k: subprocess.CompletedProcess(a, 1, b"", b"boom.cc:1"))
    with caplog.at_level(logging.WARNING, logger=nl.__name__):
        assert nl.get_lib() is None
        assert nl.get_lib() is None
    warned = [r for r in caplog.records if "build failed" in r.getMessage()]
    assert len(warned) == 1 and "boom.cc:1" in warned[0].getMessage()


def test_no_rate_records_outside_the_ledger():
    """The benchmark is BENCHMARK.json + benchmark/ and its record is
    PERF_LEDGER.jsonl: no result file of the retired bench plane sits at
    the repo root, and no knob of it is declared."""
    from mxnet_tpu import env as mxenv

    prefixes = ("BENCH_", "MULTICHIP_", "SERVE_", "FLEET_", "OBS_",
                "NUMWATCH_", "AUTOTUNE_", "MFU_")
    assert [n for n in os.listdir(REPO) if n.startswith(prefixes)] == []
    assert [n for n in mxenv.declared()
            if n.startswith("MXNET_TPU_BENCH_")] == []


def test_xprof_aot_rejection_is_counted_and_logged(caplog):
    """The AOT executable's input check is stricter than jit dispatch;
    when it rejects a call the plain jit serves it, but never silently."""
    xprof.enable()
    telemetry.reset()
    telemetry.enable()
    try:
        f = xprof.jit(lambda a: a * 2, site="t.aot_reject")

        class Rejecting:
            def __call__(self, *args):
                raise TypeError("Argument types differ from the types "
                                "for which this computation was compiled")

        arg = jnp.ones((4,), jnp.float32)
        sig = xprof.leaf_signature((arg,), None)
        f._cache[sig] = Rejecting()
        with caplog.at_level(logging.WARNING, logger=xprof.__name__):
            out = f(arg)
        assert np.allclose(np.asarray(out), 2.0)
        assert telemetry.peek("compile.aot_fallback") == 1
        assert any("t.aot_reject" in r.getMessage()
                   and "compiles this site again" in r.getMessage()
                   for r in caplog.records)
    finally:
        telemetry.reset()
        telemetry.disable()
        xprof.disable()
        xprof.reset()
