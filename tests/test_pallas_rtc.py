"""RTC tests: a kernel compiled at run time from its source through
``pallas_kernels.pallas_call`` (the interpreter on the CPU; the same
code compiles through Mosaic on a TPU)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops.pallas_kernels import pallas_available

pytestmark = pytest.mark.skipif(not pallas_available(),
                                reason="pallas unavailable")


def test_rtc_kernel():
    from mxnet_tpu.rtc import Rtc

    x = mx.nd.array(np.arange(64, dtype=np.float32).reshape(8, 8))
    y = mx.nd.ones((8, 8))
    out = mx.nd.zeros((8, 8))
    rtc = Rtc("axpy", [("x", x), ("y", y)], [("out", out)],
              "out_ref[:] = 2.0 * x_ref[:] + y_ref[:]")
    rtc.push([x, y], [out])
    np.testing.assert_allclose(out.asnumpy(),
                               2 * x.asnumpy() + 1, rtol=1e-6)


def test_rtc_multiline_kernel():
    from mxnet_tpu.rtc import Rtc

    x = mx.nd.array(np.random.randn(16, 16).astype(np.float32))
    out = mx.nd.zeros((16, 16))
    rtc = Rtc("gelu_ish",
              [("x", x)], [("out", out)],
              "v = x_ref[:]\n"
              "out_ref[:] = v * jax.nn.sigmoid(1.702 * v)")
    rtc.push([x], [out])
    v = x.asnumpy()
    np.testing.assert_allclose(out.asnumpy(),
                               v / (1 + np.exp(-1.702 * v)), rtol=1e-4)


def test_rtc_bad_source():
    from mxnet_tpu.rtc import Rtc

    x = mx.nd.ones((4, 4))
    out = mx.nd.zeros((4, 4))
    with pytest.raises(Exception):
        Rtc("bad", [("x", x)], [("out", out)], "this is not python !!!")
