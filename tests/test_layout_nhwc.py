"""NHWC layout tier: channels-last Convolution/Pooling/BatchNorm must
compute exactly what NCHW computes (weights are OIHW in both layouts,
so parity is a transpose of data only)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models


def _run(sym_net, feeds, train=False):
    shapes = {k: v.shape for k, v in feeds.items()}
    ex = sym_net.simple_bind(mx.cpu(), **shapes)
    for k, v in feeds.items():
        ex.arg_dict[k][:] = v
    if train:
        ex.forward(is_train=True)
        ex.backward()
        return ex.outputs[0].asnumpy(), ex
    return ex.forward()[0].asnumpy(), ex


def test_conv_pool_bn_nhwc_matches_nchw():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, 12, 12).astype(np.float32)
    w = rng.randn(5, 3, 3, 3).astype(np.float32)
    b = rng.randn(5).astype(np.float32)
    gamma = rng.rand(5).astype(np.float32) + 0.5
    beta = rng.randn(5).astype(np.float32)

    def tower(layout):
        data = mx.sym.Variable("data")
        net = mx.sym.Convolution(data=data, num_filter=5, kernel=(3, 3),
                                 stride=(2, 2), pad=(1, 1), layout=layout,
                                 name="c")
        net = mx.sym.BatchNorm(net, fix_gamma=False,
                               axis=-1 if layout == "NHWC" else 1,
                               name="bn")
        net = mx.sym.Activation(net, act_type="relu")
        return mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                              pool_type="max", layout=layout)

    o1, _ = _run(tower("NCHW"),
                 {"data": x, "c_weight": w, "c_bias": b,
                  "bn_gamma": gamma, "bn_beta": beta}, train=True)
    o2, _ = _run(tower("NHWC"),
                 {"data": np.ascontiguousarray(x.transpose(0, 2, 3, 1)),
                  "c_weight": w, "c_bias": b,
                  "bn_gamma": gamma, "bn_beta": beta}, train=True)
    np.testing.assert_allclose(o1, o2.transpose(0, 3, 1, 2),
                               rtol=1e-4, atol=1e-5)


def test_global_pool_nhwc():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 6, 6, 4).astype(np.float32)
    net = mx.sym.Pooling(mx.sym.Variable("data"), kernel=(1, 1),
                         global_pool=True, pool_type="avg",
                         layout="NHWC")
    out, _ = _run(net, {"data": x})
    np.testing.assert_allclose(out, x.mean(axis=(1, 2), keepdims=True),
                               rtol=1e-5)


def _resnet_feeds(net, data_shape, rng, label):
    """Every argument of ``net`` but the data: unit gammas, labels from
    ``label(shape)``, small seeded weights (OIHW: good for both layouts)."""
    arg_shapes, _, _ = net.infer_shape(data=data_shape)
    feeds = {}
    for name, shape in zip(net.list_arguments(), arg_shapes):
        if name == "data":
            continue
        if name.endswith("gamma"):
            feeds[name] = np.ones(shape, np.float32)
        elif name == "softmax_label":
            feeds[name] = label(shape).astype(np.float32)
        else:
            feeds[name] = (rng.randn(*shape) * 0.05).astype(np.float32)
    return feeds


def test_resnet50_nhwc_matches_nchw_forward():
    """Whole-tower equivalence on the flagship model (small input)."""
    rng = np.random.RandomState(2)
    nchw = models.get_resnet50(num_classes=8, small_input=True)
    nhwc = models.get_resnet50(num_classes=8, small_input=True,
                               layout="NHWC")

    x = rng.rand(2, 3, 16, 16).astype(np.float32)
    feeds = _resnet_feeds(nchw, x.shape, rng,
                          label=lambda shape: np.zeros(shape))

    o1, _ = _run(nchw, dict(feeds, data=x))
    o2, _ = _run(nhwc, dict(
        feeds, data=np.ascontiguousarray(x.transpose(0, 2, 3, 1))))
    np.testing.assert_allclose(o1, o2, rtol=1e-4, atol=1e-5)


def test_resnet50_nhwc_matches_nchw_train_step():
    """The channels-last tower trains as the NCHW one does: one forward
    and backward pass in training mode (batch statistics), same OIHW
    weights, gives the same loss output and the same weight gradients
    from the head down to the stem."""
    rng = np.random.RandomState(4)
    nchw = models.get_resnet50(num_classes=8, small_input=True)
    nhwc = models.get_resnet50(num_classes=8, small_input=True,
                               layout="NHWC")

    x = rng.rand(4, 3, 16, 16).astype(np.float32)
    feeds = _resnet_feeds(nchw, x.shape, rng,
                          label=lambda shape: rng.randint(0, 8, shape))

    o1, ex1 = _run(nchw, dict(feeds, data=x), train=True)
    o2, ex2 = _run(nhwc, dict(
        feeds, data=np.ascontiguousarray(x.transpose(0, 2, 3, 1))),
        train=True)
    np.testing.assert_allclose(o1, o2, rtol=1e-3, atol=1e-4)
    for name in ("fc1_weight", "stage1_unit1_b2_conv_weight",
                 "stem_conv_weight"):
        g1 = ex1.grad_dict[name].asnumpy()
        g2 = ex2.grad_dict[name].asnumpy()
        assert np.abs(g1).max() > 0
        np.testing.assert_allclose(g1, g2, rtol=2e-2,
                                   atol=1e-3 * np.abs(g1).max())


def test_deconvolution_nhwc_matches_nchw():
    rng = np.random.RandomState(3)
    x = rng.randn(2, 4, 6, 6).astype(np.float32)
    w = rng.randn(4, 3, 2, 2).astype(np.float32)

    def net(layout):
        return mx.sym.Deconvolution(
            mx.sym.Variable("data"), num_filter=3, kernel=(2, 2),
            stride=(2, 2), no_bias=True, layout=layout, name="d")

    o1, _ = _run(net(None), {"data": x, "d_weight": w})
    o2, _ = _run(net("NHWC"),
                 {"data": np.ascontiguousarray(x.transpose(0, 2, 3, 1)),
                  "d_weight": w})
    np.testing.assert_allclose(o1, o2.transpose(0, 3, 1, 2),
                               rtol=1e-4, atol=1e-5)


def test_invalid_layout_rejected():
    with pytest.raises(mx.base.MXNetError):
        net = mx.sym.Convolution(mx.sym.Variable("data"), num_filter=2,
                                 kernel=(3, 3), layout="NHCW", name="c")
        net.infer_shape(data=(1, 3, 8, 8))
