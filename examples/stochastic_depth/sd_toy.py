#!/usr/bin/env python
"""Stochastic depth (reference example/stochastic-depth): residual
blocks whose bodies are randomly dropped during training and scaled by
their survival probability at inference — implemented as a CustomOp
(`DropPath`), the frontend-op extension point the reference version used
for its death-rate gating.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx


@mx.operator.register("droppath")
class DropPathProp(mx.operator.CustomOpProp):
    """Bernoulli-gate the whole residual branch: train-time the branch
    is dropped (zeroed) with probability ``death_rate`` per batch;
    inference scales by the survival probability instead."""

    def __init__(self, death_rate="0.3", seed="0"):
        super().__init__(need_top_grad=True)
        self.death_rate = float(death_rate)
        self.rng = np.random.RandomState(int(seed))

    def create_operator(self, ctx, in_shapes, in_dtypes):
        prop = self

        class DropPath(mx.operator.CustomOp):
            def __init__(op):
                op.gate = 1.0

            def forward(op, is_train, req, in_data, out_data, aux):
                x = in_data[0].asnumpy()
                if is_train:
                    op.gate = float(prop.rng.rand() >= prop.death_rate)
                    out = x * op.gate
                else:
                    out = x * (1.0 - prop.death_rate)
                op.assign(out_data[0], req[0], out)

            def backward(op, req, out_grad, in_data, out_data, in_grad,
                         aux):
                op.assign(in_grad[0], req[0],
                          out_grad[0].asnumpy() * op.gate)

        return DropPath()


def res_block(x, n_hidden, death_rate, idx):
    body = mx.sym.FullyConnected(x, num_hidden=n_hidden,
                                 name="b%d_fc" % idx)
    body = mx.sym.Activation(body, act_type="relu")
    body = mx.sym.Custom(body, op_type="droppath",
                         death_rate=str(death_rate), seed=str(idx),
                         name="b%d_drop" % idx)
    return x + body


def main(seed=0, death_rate=0.3):
    rng = np.random.RandomState(seed)
    n, d = 512, 16
    y = rng.randint(0, 2, n).astype(np.float32)
    X = (rng.randn(n, d) + y[:, None] * 1.6).astype(np.float32)

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="stem")
    for i in range(3):
        net = res_block(net, 32, death_rate, i)
    net = mx.sym.FullyConnected(net, num_hidden=2, name="cls")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    model = mx.model.FeedForward.create(
        net, X=mx.io.NDArrayIter(X, y, batch_size=64, shuffle=True),
        num_epoch=8, learning_rate=0.1, ctx=mx.cpu())
    acc = (model.predict(mx.io.NDArrayIter(X, batch_size=64))
           .argmax(axis=1) == y).mean()
    print("accuracy with stochastic depth: %.3f" % acc)
    assert acc > 0.9, acc
    print("stochastic depth OK")


if __name__ == "__main__":
    main()
