#!/usr/bin/env python
"""SVM output layer (reference example/svm_mnist): the same MLP trained
with SVMOutput (L2 hinge and L1 hinge) instead of softmax.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx


def build(use_linear):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SVMOutput(net, margin=1.0, regularization_coefficient=1.0,
                            use_linear=use_linear, name="svm")


def main(seed=0):
    rng = np.random.RandomState(seed)
    n, d = 512, 16
    y = rng.randint(0, 4, n).astype(np.float32)
    centers = rng.randn(4, d) * 2.5
    X = (centers[y.astype(int)] + rng.randn(n, d) * 0.6).astype(np.float32)
    for use_linear, name in ((False, "L2-SVM"), (True, "L1-SVM")):
        model = mx.model.FeedForward.create(
            build(use_linear),
            X=mx.io.NDArrayIter(X, y, batch_size=64, shuffle=True,
                                label_name="svm_label"),
            num_epoch=10, learning_rate=0.05, ctx=mx.cpu())
        acc = (model.predict(mx.io.NDArrayIter(X, batch_size=64))
               .argmax(axis=1) == y).mean()
        print("%s train accuracy: %.3f" % (name, acc))
        assert acc > 0.9, (name, acc)
    print("SVM outputs OK")


if __name__ == "__main__":
    main()
