"""Cross-dtype operator consistency (the reference's GPU-vs-CPU
validation tier: tests/python/gpu/test_operator_gpu.py re-ran every op
through check_consistency across ctx x dtype configs with per-dtype
tolerances). Here the axis is dtype (fp16/fp32), exercised per core op
family; the accelerator-vs-CPU axis needs the chip and lives in
tools/tpu_consistency.py.
"""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.test_utils import check_consistency


def _cfgs(**shapes):
    return [
        {"ctx": mx.cpu(), **shapes},
        {"ctx": mx.cpu(), **shapes,
         "type_dict": {"data": np.float16}},
    ]


def test_consistency_fullyconnected():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data=data, num_hidden=8, name="fc")
    check_consistency(net, _cfgs(data=(4, 6)))


def test_consistency_convolution_pooling():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data=data, kernel=(3, 3), num_filter=4,
                             pad=(1, 1), name="conv")
    net = mx.sym.Pooling(data=net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    check_consistency(net, _cfgs(data=(2, 3, 8, 8)))


def test_consistency_activation_family():
    for act in ("relu", "sigmoid", "tanh", "softrelu"):
        data = mx.sym.Variable("data")
        net = mx.sym.Activation(data=data, act_type=act)
        check_consistency(net, _cfgs(data=(4, 8)))


def test_consistency_batchnorm():
    data = mx.sym.Variable("data")
    net = mx.sym.BatchNorm(data=data, fix_gamma=False, name="bn")
    # BN in fp16 accumulates stats with fp16 inputs; loosen nothing —
    # stats are computed in >= f32 internally (ops/nn.py)
    check_consistency(net, _cfgs(data=(4, 3, 6, 6)))


def test_consistency_softmax_and_lrn():
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxActivation(data=data)
    check_consistency(net, _cfgs(data=(4, 10)))
    net = mx.sym.LRN(data=data, nsize=3)
    check_consistency(net, _cfgs(data=(2, 4, 5, 5)))


def test_consistency_elementwise_reduce():
    data = mx.sym.Variable("data")
    net = mx.sym.sum(data=data, axis=1)
    check_consistency(net, _cfgs(data=(3, 4, 5)), grad_req="null")
