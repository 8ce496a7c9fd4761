"""The chip's compiler on this repo's Pallas kernels at real widths, for a
TPU v5e that is described and not attached: Mosaic refuses here what it
would refuse on the chip (a slice off the tiling, more VMEM than a kernel
may use), which the interpreter never does. Nothing runs; no time comes
from here. The topology is described inside a fixture, in this file only:
one process may load the TPU's library, and only a test that has started
may ask for it.
"""
import os

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


@pytest.mark.parametrize("heads,dk,dv,chunk", [
    (15, 96, 192, 64),      # the benchmark's cell: odd heads, part lanes
    (4, 128, 256, 128),     # the widest the rule admits
    (5, 8, 8, 128),         # the narrowest widths at the longest chunk
    (16, 64, 64, 64)],
    ids=["cell", "widest", "narrowest", "half-lanes"])
def test_delta_chunk_kernels_compile_for_the_chip(one_chip, heads, dk, dv,
                                                  chunk):
    """Every shape ``delta_chunk_applicable`` admits has to fit the 16 MB
    of scoped VMEM with the heads a step ``_delta_heads`` gives it."""
    from mxnet_tpu.ops import pallas_kernels as pk

    assert pk.delta_chunk_applicable((heads, dk, dv), chunk,
                                     jnp.dtype("float32"))
    b, t = 1, 4 * chunk

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    args = (shape(b, t, heads, dk), shape(b, t, heads, dk),
            shape(b, t, heads, dv), shape(b, t, heads), shape(b, t, heads))
    forward = jax.jit(lambda *a: pk._delta_chunk_forward(
        *a, chunk=chunk, with_states=True)).lower(*args).compile()
    backward = jax.jit(lambda *a: pk._delta_chunk_backward(
        *a, chunk=chunk)).lower(
            *args, shape(b, t // chunk, heads, dk, dv),
            shape(b, t, heads, dv)).compile()
    for compiled in (forward, backward):
        assert "tpu_custom_call" in compiled.as_text()


def test_splash_attention_at_256_wide_heads_compiles_for_the_chip(one_chip):
    """Latent attention's kernel call at the benchmark's cell: 20 one-head
    groups of 256 columns over 8,192 positions, forward and backward."""
    from mxnet_tpu.ops import attention

    b, t, h, d = 1, 8192, 20, 256

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return attention.attend_splash(q, k, v).astype(jnp.float32).sum()

    # tests/conftest.py asks for "highest", which Mosaic refuses of bfloat16
    # operands; a benchmark run leaves the default
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shape(b, h, 1, t, d), shape(b, h, t, d),
            shape(b, h, t, d)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


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
        rows_, weights, slot, block_expert, nblocks = layout
        y = moe.grouped_experts_gated(
            x, w_gate, w_up, w_down, wts, rows_,
            jax.lax.stop_gradient(weights), slot, block_expert, nblocks)
        return y.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        shape((rows, h)), shape((h, e), jnp.float32), shape((held, h, f)),
        shape((held, h, f)), shape((held, f, h))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
