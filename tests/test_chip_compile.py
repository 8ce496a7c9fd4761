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
