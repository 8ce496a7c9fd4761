"""The chip's compiler on this repo's Pallas kernels at real widths, for a
TPU v5e that is described and not attached: Mosaic refuses here what it
would refuse on the chip (a slice off the tiling, more VMEM than a kernel
may use), which the interpreter never does. Nothing runs; no time comes
from here. The topology is described inside a fixture, in this file only:
one process may load the TPU's library, and only a test that has started
may ask for it.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here, or its lock is held
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("heads,dk,dv,chunk,channel", [
    (15, 96, 192, 64, False),   # the benchmark's cell: odd heads, part lanes
    (4, 128, 256, 128, False),  # the widest the rule admits
    (5, 8, 8, 128, False),      # the narrowest widths at the longest chunk
    (16, 64, 64, 64, False),
    (32, 128, 128, 64, True),   # the Ling cell: a decay a key channel
    (4, 128, 256, 64, True),    # ... at the widest values
    (32, 128, 128, 64, False)],  # the Qwen3-Next cell: 16 key heads repeated
    ids=["cell", "widest", "narrowest", "half-lanes", "channel-cell",
         "channel-widest", "grouped-heads-cell"])
def test_delta_chunk_kernels_compile_for_the_chip(one_chip, heads, dk, dv,
                                                  chunk, channel):
    """Every shape ``delta_chunk_applicable`` (a decay a key ``channel``:
    ``delta_channel_applicable``) admits has to fit the 16 MB of scoped
    VMEM with the heads a step ``_delta_heads`` gives it."""
    from mxnet_tpu.ops import pallas_kernels as pk

    rule = pk.delta_channel_applicable if channel \
        else pk.delta_chunk_applicable
    assert rule((heads, dk, dv), chunk, jnp.dtype("float32"))
    b, t = 1, 4 * chunk

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    gate = (b, t, heads, dk) if channel else (b, t, heads)
    args = (shape(b, t, heads, dk), shape(b, t, heads, dk),
            shape(b, t, heads, dv), shape(*gate), shape(b, t, heads))
    forward = jax.jit(lambda *a: pk._delta_chunk_forward(
        *a, chunk=chunk, with_states=True)).lower(*args).compile()
    backward = jax.jit(lambda *a: pk._delta_chunk_backward(
        *a, chunk=chunk)).lower(
            *args, shape(b, t // chunk, heads, dk, dv),
            shape(b, t, heads, dv)).compile()
    for compiled in (forward, backward):
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("heads,key_heads,dv,chunk,channel,floor,dtype", [
    (32, 32, 128, 64, True, -5.0, "bfloat16"),  # the Ling cell
    (32, 16, 128, 64, False, 0.0, "bfloat16"),  # the Qwen3-Next cell
    (4, 4, 256, 64, True, 0.0, "float32"),      # the softplus gate, widest
    (8, 2, 128, 16, False, 0.0, "bfloat16")],   # four value heads a key head
    ids=["ling-cell", "qwen3-next-cell", "softplus-gate-values-256",
         "four-a-key-head-chunk-16"])
def test_delta_rows_kernels_compile_for_the_chip(one_chip, heads, key_heads,
                                                 dv, chunk, channel, floor,
                                                 dtype):
    """The chunk kernels' row-major entry (``delta_rows_applicable``): blocks
    of a step's heads' lanes off the op's ``[rows, H * K]`` arrays in the
    compute dtype, the casts, the normalisations and a channel's gate in
    VMEM, within the 16 MB of scoped VMEM at the heads a step
    ``_delta_heads`` gives."""
    from mxnet_tpu.ops import pallas_kernels as pk

    dk, t = 128, 4 * chunk
    assert pk.delta_rows_applicable((heads, dk, dv), key_heads, chunk, t,
                                    channel)
    spec = pk.DeltaRows(t, heads, key_heads, dk, dv, chunk, floor, 1e-6)

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def rows(width):
        return shape(2 * t, width, dtype=jnp.dtype(dtype))

    scalars, gate_row = shape(2, t, heads), shape(1, heads * dk)
    args = (rows(key_heads * dk), rows(key_heads * dk), rows(heads * dv)) + (
        (rows(heads * dk), scalars, gate_row, gate_row) if channel
        else (scalars, scalars, None, None))
    forward = jax.jit(lambda *a: pk._delta_rows_forward(
        *a, spec=spec, with_states=True)).lower(*args).compile()
    backward = jax.jit(lambda *a: pk._delta_rows_backward(
        *a, spec=spec)).lower(
            *args, shape(2, t // chunk, heads, dk, dv),
            rows(heads * dv)).compile()
    for compiled in (forward, backward):
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("h,group,d,dv,window", [
    (20, 1, 256, 256, 0), (8, 4, 64, 64, 0), (32, 1, 256, 128, 0),
    (2, 8, 256, 256, 0), (8, 6, 128, 128, 0), (8, 8, 128, 128, 512)],
    ids=["256-wide", "64-wide-grouped", "256-wide-keys-128-wide-values",
         "256-wide-grouped", "128-wide-groups-of-6",
         "128-wide-groups-of-8-window-512"])
def test_splash_attention_compiles_for_the_chip(one_chip, h, group, d, dv,
                                                window):
    """The kernel call at six of the benchmark's cells' layers: latent
    attention's 20 one-head groups of 256 columns, LFM2's 8 groups of four
    heads of 64, half a lane tile, Ling's 32 heads whose 192-wide keys go
    widened to 256 beside values of 128, Qwen3-Next's 2 groups of eight heads
    of 256, and Laguna's 8 groups of six (full layers) and of eight under a
    window of 512 (both passes over the band's 31 pairs of blocks), over
    8,192 positions: this repo's forward kernel and its one backward kernel,
    two custom calls where JAX's own three passes made three, and no ``[T,
    T]`` array in either pass."""
    from mxnet_tpu.ops import attention

    b, t = 1, 8192

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return attention.attend_splash(q, k, v, window=window).astype(
            jnp.float32).sum()

    # tests/conftest.py asks for "highest", which Mosaic refuses of bfloat16
    # operands; a benchmark run leaves the default
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shape(b, h, group, t, d), shape(b, h, t, d),
            shape(b, h, t, dv)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for which in ("forward", "backward"):
        assert "%s_attention_%s" % ("window" if window else "causal",
                                    which) in text
    assert "splash_mqa" not in text
    assert "8192,8192" not in text


@pytest.mark.parametrize("h,group,d,dv,window", [
    (20, 1, 256, 256, 0), (8, 4, 64, 64, 0), (32, 1, 256, 128, 0),
    (2, 8, 256, 256, 0), (2, 16, 128, 128, 0), (15, 1, 128, 128, 0),
    (8, 6, 128, 128, 0), (8, 8, 128, 128, 512), (8, 8, 128, 128, 700)],
    ids=["glm", "lfm2", "ling", "qwen3-next", "nemotron", "olmo",
         "laguna-full", "laguna-window", "window-of-a-block-and-a-third"])
def test_attention_backward_compiles_for_the_chip(one_chip, h, group, d, dv,
                                                  window):
    """The one-kernel backward pass at the seven language cells' shapes
    (Laguna's two kinds of layer; under a window the grid is the band's
    pairs, the edge's mask takes a scalar from SMEM) over 8,192 positions: the head's float32 ``dk`` and ``dv`` resident in VMEM
    (16 MiB at 256 + 256 columns, above Mosaic's default limit), the
    contraction over a pair's key rows for ``dq``, the dynamic row slices of
    the accumulators and half-lane heads are Mosaic's to refuse."""
    from mxnet_tpu.ops import pallas_kernels as pk

    b, t = 1, 8192

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    assert pk.attention_applicable(t, d, dv, jnp.bfloat16)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(
            lambda *a: pk.attention_backward(*a, window=window)).lower(
            shape(b, h, group, t, d), shape(b, h, t, d), shape(b, h, t, dv),
            shape(b, h, group, t, dv), shape(b, h, group, t, dtype="float32"),
            shape(b, h, group, t, dtype="float32")).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("h,group,d,dv,window", [
    (20, 1, 256, 256, 0), (8, 4, 64, 64, 0), (32, 1, 256, 128, 0),
    (2, 8, 256, 256, 0), (2, 16, 128, 128, 0), (15, 1, 128, 128, 0),
    (8, 6, 128, 128, 0), (8, 8, 128, 128, 512), (8, 8, 128, 128, 700)],
    ids=["glm", "lfm2", "ling", "qwen3-next", "nemotron", "olmo",
         "laguna-full", "laguna-window", "window-of-a-block-and-a-third"])
def test_attention_forward_compiles_for_the_chip(one_chip, h, group, d, dv,
                                                 window):
    """The forward kernel at the seven language cells' shapes over 8,192
    positions: a head's keys and values whole in VMEM (8 MiB at 256 + 256
    columns, double-buffered: above Mosaic's default limit) and the key
    blocks a loop of dynamic length inside a query block's step; scores by
    key rows, so a position's running max, sum and log-sum-exp are one
    number of a ``[1, 512]`` row; the group's query heads a step as
    ``_attention_forward_heads`` gives them (eight of Nemotron's sixteen),
    their float32 ``[Dv, 512]`` accumulators in VMEM; the transposed ``v``
    block and the accumulator's one transpose a query block, half-lane
    heads and the column parts' slices under a window are Mosaic's to
    refuse. One custom call; the log-sum-exp leaves as ``[.., 1, T]`` rows,
    so no lane is cut out of a ``[.., T, 128]`` array after it."""
    from mxnet_tpu.ops import pallas_kernels as pk

    b, t = 1, 8192

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    assert pk.attention_applicable(t, d, dv, jnp.bfloat16)
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(
            lambda *a: pk.attention_forward(*a, window=window)).lower(
            shape(b, h, group, t, d), shape(b, h, t, d),
            shape(b, h, t, dv)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert ("window" if window else "causal") + "_attention_forward" in text
    assert not re.search(r"f32\[[0-9,]*8192,128\]", text)


@pytest.mark.parametrize("batch,t,heads,d,turned,dtype", [
    (1, 8192, 20, 256, 64, "bfloat16"),     # GLM's queries and keys
    (1, 8192, 32, 128, 128, "bfloat16"),    # Nemotron's queries
    (1, 8192, 15, 128, 0, "bfloat16"),      # Olmo's, and every value
    (2, 1024, 3, 256, 256, "bfloat16"),     # a partner in the other tile
    (1, 1024, 3, 256, 192, "bfloat16"),     # turned columns over two tiles
    (3, 640, 2, 128, 2, "float32"),         # one pair; 128-row tiles
    (1, 8192, 32, 64, 64, "bfloat16"),      # LFM2's queries: two heads a tile
    (1, 8192, 8, 64, 0, "bfloat16"),        # LFM2's values
    (2, 1024, 2, 64, 16, "float32"),        # a part of a 64-wide head
    (1, 8192, 32, 256, 64, "bfloat16"),     # Ling's keys, widened from 192
    (1, 8192, 2, 256, 64, "bfloat16"),      # Qwen3-Next's keys: 2 heads
    (1, 8192, 48, 128, 64, "bfloat16")],    # Laguna's full layers' queries
    ids=["glm", "nemotron", "plain", "two-tiles", "tile-and-a-half",
         "one-pair", "lfm2", "lfm2-plain", "half-lanes-part", "ling",
         "qwen3-next", "laguna-yarn"])
def test_attention_relayout_passes_compile_for_the_chip(one_chip, batch, t,
                                                        heads, d, turned,
                                                        dtype):
    """``CausalAttention``'s way to the splash kernel and back, at every
    kind of shape ``_splash_applies`` admits: the lanes' rotation by half
    the turned width and the select are Mosaic's to refuse."""
    from mxnet_tpu.ops import attention, pallas_kernels as pk

    half = turned // 2
    # Laguna's: half of a 128-wide head under YaRN's tables
    scaling = (64.0, 4096, 64.0, 1.0, 0.0) if (heads, d) == (48, 128) \
        else None
    tables = attention.relayout_tables(t, 1e4, half, d, scaling)
    how = dict(batch=batch, heads=heads, half=half, scale=0.125)
    there = jax.jit(lambda x: pk.attention_relayout(x, tables, **how)).lower(
        jax.ShapeDtypeStruct((batch * t, heads * d), dtype,
                             sharding=one_chip)).compile()
    back = jax.jit(lambda g: pk.attention_relayout(
        g, tables, back=True, **how)).lower(
            jax.ShapeDtypeStruct((batch, heads, t, d), dtype,
                                 sharding=one_chip)).compile()
    for compiled in (there, back):
        assert "tpu_custom_call" in compiled.as_text()


def test_gated_experts_compile_for_the_chip(one_chip):
    """The gated grouped products at the cell's sizes (8 held experts of
    2,048 x 1,536, 8,192 rows, top-4 of 64): both written passes."""
    from mxnet_tpu.ops import moe

    rows, h, f, held, e, k = 8192, 2048, 1536, 8, 64, 4

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(x, router, w_gate, w_up, w_down):
        eid, wts = moe.route(x, router, jnp.zeros((e,), jnp.float32), k, 1.8)
        *layout, _ = moe.plan(eid, wts, 0, held,
                              moe.block_rows(rows, k, e))
        rows_, weights, *rest = layout
        y = moe.grouped_experts_gated(
            x, w_gate, w_up, w_down, wts, rows_,
            jax.lax.stop_gradient(weights), *rest)
        return y.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        shape((rows, h)), shape((h, e), jnp.float32), shape((held, h, f)),
        shape((held, h, f)), shape((held, f, h))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


EXPERT_CELLS = {
    # rows, h, f, held, top_k, experts, gated, dtype
    "nemotron-cell": (8192, 2688, 1856, 8, 6, 128, False, "bfloat16"),
    "glm-cell": (8192, 2048, 1536, 8, 4, 64, True, "bfloat16"),
    "ling-cell": (8192, 2560, 768, 8, 8, 512, True, "bfloat16"),
    "qwen3-next-cell": (8192, 2048, 512, 32, 10, 512, True, "bfloat16"),
    "laguna-cell": (8192, 2048, 512, 32, 8, 256, True, "bfloat16"),
    "narrowest-float32": (64, 128, 8, 2, 2, 4, True, "float32"),
    "narrowest-bfloat16": (128, 128, 16, 2, 2, 4, False, "bfloat16"),
}


@pytest.mark.parametrize("pass_", ["forward", "backward"])
@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_grouped_experts_kernels_compile_for_the_chip(one_chip, cell, pass_):
    """Both cells' shapes (the widest the rule admits; the Nemotron cell's
    ``f`` of 1856 hangs over its backward kernel's last tile, and its rows
    of 21 lane tiles pack into 11 words' tiles) and the narrowest, at the
    static bound of blocks, with the VMEM the rule reckons: Mosaic takes
    the row kernels' dynamic rows and resident tables, the tiles and the
    accumulators."""
    from mxnet_tpu.ops import moe
    from mxnet_tpu.ops import pallas_kernels as pk

    rows, h, f, held, k, e, gated, dtype = EXPERT_CELLS[cell]
    dtype = jnp.dtype(dtype)
    block = moe.block_rows(rows, k, e)
    assert pk.grouped_experts_applicable(h, f, block, dtype, gated, rows)
    assert pk._experts_tiles(h, f, block, gated, dtype.itemsize)[2] \
        <= pk._EXPERTS_VMEM
    length = moe.layout_length(rows, k, held, block)

    def shape(dims, dt=dtype):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    ws = (shape((held, h, f)),) * (2 if gated else 1) + (shape((held, f, h)),)
    layout = (shape((length,), jnp.int32), shape((length,), jnp.float32),
              shape((length // block,), jnp.int32), shape((1,), jnp.int32))
    with jax.default_matmul_precision("default"):
        if pass_ == "forward":
            compiled = jax.jit(lambda x, ws, *lay: pk._experts_forward(
                x, ws, *lay, gated=gated)).lower(
                    shape((rows, h)), ws, *layout).compile()
        else:
            compiled = jax.jit(lambda x, ws, dy, *lay: pk._experts_backward(
                x, ws, *lay, dy, gated=gated)).lower(
                    shape((rows, h)), ws, shape((rows, h)), *layout).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # gather(s), products, scatter: no loop of XLA ops over the blocks
    assert text.count("custom_call_target=\"tpu_custom_call\"") == \
        (3 if pass_ == "forward" else 4)
    assert " while(" not in text


@pytest.mark.parametrize("h,f,rows,why", [
    (96, 128, 128, "a row of 96 is not whole lanes"),
    (128, 20, 128, "an inner width of 20 is not whole sublanes"),
    (128, 128, 72, "a block of 9 rows is no sublane tile")])
def test_shapes_the_rule_refuses_take_the_xla_loop(h, f, rows, why):
    """What ``grouped_experts_applicable`` refuses lowers as the loop of XLA
    products, and the op counts which body it took."""
    from mxnet_tpu import symbol as sym
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import moe
    from mxnet_tpu.ops import pallas_kernels as pk

    block = moe.block_rows(rows, 2, 4)
    assert not pk.grouped_experts_applicable(h, f, block, jnp.float32,
                                             False, rows), why
    net = sym.RoutedExperts(num_experts=4, num_held=2, top_k=2, num_hidden=f,
                            data=sym.Variable("data"), name="x")
    telemetry.reset()
    telemetry.enable()
    try:
        net.simple_bind(mx_cpu(), data=(rows, h)).forward(is_train=False)
        assert telemetry.peek("lower.experts_kernel.xla_loop") == 1
        assert not telemetry.peek("lower.experts_kernel.pallas_grouped")
    finally:
        telemetry.disable()


def mx_cpu():
    import mxnet_tpu as mx

    return mx.cpu()
