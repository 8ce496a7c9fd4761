"""NDArray tests (reference tests/python/unittest/test_ndarray.py)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd


def test_ndarray_creation():
    a = nd.zeros((3, 4))
    assert a.shape == (3, 4)
    assert a.dtype == np.float32
    assert np.all(a.asnumpy() == 0)
    b = nd.ones((2,), dtype=np.int32)
    assert b.dtype == np.int32
    c = nd.full((2, 2), 7.5)
    assert np.all(c.asnumpy() == 7.5)
    d = nd.array([[1, 2], [3, 4]])
    assert d.dtype == np.float32
    assert d.asnumpy().tolist() == [[1, 2], [3, 4]]


def test_ndarray_elementwise():
    rng = np.random.RandomState(0)
    for _ in range(3):
        a_np = rng.randn(4, 5).astype(np.float32)
        b_np = rng.rand(4, 5).astype(np.float32) + 0.5
        a, b = nd.array(a_np), nd.array(b_np)
        np.testing.assert_allclose((a + b).asnumpy(), a_np + b_np, rtol=1e-5)
        np.testing.assert_allclose((a - b).asnumpy(), a_np - b_np, rtol=1e-5)
        np.testing.assert_allclose((a * b).asnumpy(), a_np * b_np, rtol=1e-5)
        np.testing.assert_allclose((a / b).asnumpy(), a_np / b_np, rtol=1e-5)
        np.testing.assert_allclose((a + 3).asnumpy(), a_np + 3, rtol=1e-5)
        np.testing.assert_allclose((2 - a).asnumpy(), 2 - a_np, rtol=1e-5)
        np.testing.assert_allclose((-a).asnumpy(), -a_np, rtol=1e-5)


def test_ndarray_inplace():
    a = nd.ones((2, 3))
    a += 2
    np.testing.assert_allclose(a.asnumpy(), np.full((2, 3), 3.0))
    a *= 2
    np.testing.assert_allclose(a.asnumpy(), np.full((2, 3), 6.0))
    b = nd.ones((2, 3))
    a -= b
    np.testing.assert_allclose(a.asnumpy(), np.full((2, 3), 5.0))


def test_ndarray_setitem_getitem():
    a = nd.zeros((4, 4))
    a[:] = 5
    assert np.all(a.asnumpy() == 5)
    a[1:3] = 1
    expected = np.full((4, 4), 5.0)
    expected[1:3] = 1
    np.testing.assert_allclose(a.asnumpy(), expected)
    sl = a[1:3]
    assert sl.shape == (2, 4)
    assert np.all(sl.asnumpy() == 1)
    np_b = np.arange(16).reshape(4, 4).astype(np.float32)
    b = nd.array(np_b)
    np.testing.assert_allclose(b[2].asnumpy(), np_b[2])


def test_ndarray_reshape_transpose():
    a_np = np.arange(24).reshape(2, 3, 4).astype(np.float32)
    a = nd.array(a_np)
    np.testing.assert_allclose(a.reshape((6, 4)).asnumpy(),
                               a_np.reshape(6, 4))
    np.testing.assert_allclose(a.reshape((-1, 4)).asnumpy(),
                               a_np.reshape(-1, 4))
    np.testing.assert_allclose(nd.transpose(a).asnumpy(), a_np.T)
    np.testing.assert_allclose(a.T.asnumpy(), a_np.T)


def test_ndarray_functions():
    a_np = np.random.rand(3, 4).astype(np.float32) + 0.1
    a = nd.array(a_np)
    np.testing.assert_allclose(nd.exp(a).asnumpy(), np.exp(a_np), rtol=1e-5)
    np.testing.assert_allclose(nd.log(a).asnumpy(), np.log(a_np), rtol=1e-5)
    np.testing.assert_allclose(nd.sqrt(a).asnumpy(), np.sqrt(a_np), rtol=1e-5)
    np.testing.assert_allclose(nd.square(a).asnumpy(), a_np ** 2, rtol=1e-5)
    np.testing.assert_allclose(nd.sum(a).asnumpy(), [a_np.sum()], rtol=1e-5)
    np.testing.assert_allclose(nd.max(a).asnumpy(), [a_np.max()], rtol=1e-5)
    np.testing.assert_allclose(
        nd.norm(a).asnumpy(), [np.sqrt((a_np ** 2).sum())], rtol=1e-5)
    b_np = np.random.rand(4, 5).astype(np.float32)
    b = nd.array(b_np)
    np.testing.assert_allclose(nd.dot(a, b).asnumpy(), a_np.dot(b_np),
                               rtol=1e-4)
    np.testing.assert_allclose(nd.clip(a, 0.2, 0.8).asnumpy(),
                               np.clip(a_np, 0.2, 0.8), rtol=1e-6)
    np.testing.assert_allclose(nd.maximum(a, 0.5).asnumpy(),
                               np.maximum(a_np, 0.5), rtol=1e-6)


def test_ndarray_onehot():
    idx = nd.array([0, 2, 1])
    out = nd.zeros((3, 3))
    nd.onehot_encode(idx, out)
    np.testing.assert_allclose(out.asnumpy(), np.eye(3)[[0, 2, 1]])
    picked = nd.choose_element_0index(out, idx)
    np.testing.assert_allclose(picked.asnumpy(), [1, 1, 1])


def test_ndarray_copy():
    a = nd.array(np.random.rand(3, 3).astype(np.float32))
    b = a.copy()
    b += 1
    assert not np.allclose(a.asnumpy(), b.asnumpy())
    c = nd.zeros((3, 3))
    a.copyto(c)
    np.testing.assert_allclose(a.asnumpy(), c.asnumpy())
    d = a.as_in_context(mx.cpu(1))
    assert d.context == mx.cpu(1)
    np.testing.assert_allclose(a.asnumpy(), d.asnumpy())


def test_ndarray_saveload(tmp_path):
    fname = str(tmp_path / "arrays.bin")
    arrays = [nd.array(np.random.rand(3, 4).astype(np.float32)),
              nd.array(np.arange(5).astype(np.int32))]
    nd.save(fname, arrays)
    loaded = nd.load(fname)
    assert len(loaded) == 2
    for orig, back in zip(arrays, loaded):
        np.testing.assert_allclose(orig.asnumpy(), back.asnumpy())
        assert orig.dtype == back.dtype
    d = {"weight": arrays[0], "idx": arrays[1]}
    nd.save(fname, d)
    loaded = nd.load(fname)
    assert set(loaded.keys()) == {"weight", "idx"}
    np.testing.assert_allclose(loaded["weight"].asnumpy(),
                               arrays[0].asnumpy())


def test_ndarray_concatenate():
    a = nd.array(np.ones((2, 3), dtype=np.float32))
    b = nd.array(np.zeros((3, 3), dtype=np.float32))
    c = nd.concatenate([a, b], axis=0)
    assert c.shape == (5, 3)
    np.testing.assert_allclose(c.asnumpy()[:2], 1)
    np.testing.assert_allclose(c.asnumpy()[2:], 0)


def test_ndarray_waitall():
    a = nd.ones((100, 100))
    for _ in range(10):
        a = a * 1.0001
    nd.waitall()
    assert a.asnumpy().shape == (100, 100)


def test_ndarray_64bit_dtype_honesty():
    """Requested 64-bit dtypes are honored (x64 on) or rejected loudly
    — never silently narrowed (the reference's mshadow dtype tables
    honor them; jax with x64 off would truncate)."""
    import subprocess
    import sys

    from mxnet_tpu.base import MXNetError

    for ctor in (lambda: nd.zeros((2,), dtype=np.int64),
                 lambda: nd.ones((2,), dtype=np.float64),
                 lambda: nd.full((2,), 3, dtype=np.uint64),
                 lambda: nd.arange(0, 4, dtype=np.int64),
                 lambda: nd.array([1, 2], dtype=np.float64),
                 lambda: nd.ones((2,)).astype(np.int64)):
        with pytest.raises(MXNetError, match="x64"):
            ctor()

    # implicit python-int/float sources still take the reference default
    # (float32, mx_real_t) without erroring
    assert nd.array([1, 2, 3]).dtype == np.float32

    # with x64 enabled the request is honored end-to-end
    code = (
        "import jax; jax.config.update('jax_enable_x64', True)\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "from mxnet_tpu import ndarray as nd\n"
        "a = nd.zeros((2,), dtype=np.int64)\n"
        "assert a.dtype == np.int64, a.dtype\n"
        "b = nd.array([1.5, 2.5], dtype=np.float64)\n"
        "assert b.dtype == np.float64, b.dtype\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="1")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]


def test_load_64bit_checkpoint_narrows_with_warning():
    """nd.load of a 64-bit container (saved under x64, or written by the
    reference) must not hard-fail when x64 is off: it narrows loudly."""
    import io as _io
    import subprocess
    import sys
    import warnings

    # produce a float64+int64 container in an x64 subprocess
    path = "/tmp/x64_container.nd"
    code = (
        "import jax; jax.config.update('jax_enable_x64', True)\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "from mxnet_tpu import ndarray as nd\n"
        "nd.save(%r, {'w': nd.array(np.array([1.5, 2.5]), "
        "dtype=np.float64), 'i': nd.array(np.array([3, 2**40]), "
        "dtype=np.int64)})\n" % path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="1")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        loaded = nd.load(path)
    assert loaded["w"].dtype == np.float32
    assert loaded["i"].dtype == np.int32
    assert any("narrowing" in str(x.message) for x in w)
    np.testing.assert_allclose(loaded["w"].asnumpy(), [1.5, 2.5])


def test_array_implicit_uint64_takes_default():
    """Implicit uint64 sources take the reference float32 default instead
    of reaching jax's silent uint32 truncation."""
    a = nd.array(np.array([2 ** 40, 1], dtype=np.uint64))
    assert a.dtype == np.float32
    np.testing.assert_allclose(a.asnumpy(), [float(2 ** 40), 1.0])


def test_shares_buffer_tristate():
    """_shares_buffer: True/False only when VERIFIED via buffer
    pointers; None when unverifiable (callers must copy defensively)."""
    import jax

    from mxnet_tpu.ndarray import _shares_buffer

    a = mx.nd.ones((2, 2))._data
    b = mx.nd.ones((2, 2))._data
    assert _shares_buffer(a, a) is True
    assert _shares_buffer(a, b) is False
    # device_put onto the same device may alias: whatever it returns,
    # the answer must be verified, never None, on a single local device
    c = jax.device_put(a, list(a.devices())[0])
    assert _shares_buffer(a, c) in (True, False)

    class _NoPointer:
        """Array-like with neither unsafe_buffer_pointer nor shards."""

    assert _shares_buffer(_NoPointer(), _NoPointer()) is None


def test_shares_buffer_sharded_via_addressable_shards():
    """Arrays whose only pointer access is per-shard (sharded arrays:
    unsafe_buffer_pointer raises) are verified by shard-pointer
    intersection instead of answering False blindly."""
    from mxnet_tpu.ndarray import _shares_buffer

    class _Shard:
        def __init__(self, ptr):
            self.data = self
            self._ptr = ptr

        def unsafe_buffer_pointer(self):
            return self._ptr

    class _Sharded:
        def __init__(self, ptrs):
            self.addressable_shards = [_Shard(p) for p in ptrs]

        def unsafe_buffer_pointer(self):
            raise RuntimeError("sharded array has no single buffer")

    assert _shares_buffer(_Sharded([1, 2]), _Sharded([2, 3])) is True
    assert _shares_buffer(_Sharded([1, 2]), _Sharded([3, 4])) is False
    assert _shares_buffer(_Sharded([]), _Sharded([1])) is None


def test_copyto_defensive_on_unverifiable_aliasing(monkeypatch):
    """When aliasing cannot be verified, copyto must still produce a
    buffer that survives donation of the source — i.e. it copies."""
    from mxnet_tpu import ndarray as ndmod

    monkeypatch.setattr(ndmod, "_shares_buffer", lambda a, b: None)
    src = mx.nd.array(np.arange(4, dtype=np.float32))
    dst = mx.nd.zeros((4,))
    src.copyto(dst)
    assert dst._data is not src._data
    np.testing.assert_array_equal(dst.asnumpy(),
                                  np.arange(4, dtype=np.float32))


def test_full_write_from_numpy_stays_on_the_arrays_device():
    """``a[:] = numpy`` keeps ``a`` where it lives: it used to land on
    JAX's default device, so on a machine whose default is the
    accelerator the module's host copies of the parameters moved there
    at every ``get_params``."""
    import jax

    cpus = jax.devices("cpu")
    if len(cpus) < 4:
        pytest.skip("needs several host devices")
    a = mx.nd.zeros((4, 3), ctx=mx.cpu(3))
    a[:] = np.arange(12, dtype=np.float32).reshape(4, 3)
    assert a._data.devices() == {cpus[3]}
    np.testing.assert_array_equal(a.asnumpy(),
                                  np.arange(12).reshape(4, 3))
    a[:] = mx.nd.ones((4, 3), ctx=mx.cpu(0))       # an NDArray brings its own
    assert a.asnumpy().sum() == 12
