#!/usr/bin/env python
"""Adversarial examples via FGSM (reference example/adversary).

Trains a small MLP classifier, then computes the fast-gradient-sign
perturbation from the executor's *data* gradient (``grad_req`` on the
input — the same executor mechanics the reference notebook used) and
shows accuracy collapsing on the perturbed batch.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx


def build_net():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def main(epsilon=1.0, seed=0):
    rng = np.random.RandomState(seed)
    # 4 gaussian blobs in 16-d
    n, d = 512, 16
    y = rng.randint(0, 4, n).astype(np.float32)
    centers = rng.randn(4, d) * 1.5
    X = (centers[y.astype(int)] + rng.randn(n, d) * 0.5).astype(np.float32)

    net = build_net()
    model = mx.model.FeedForward.create(
        net, X=mx.io.NDArrayIter(X, y, batch_size=64, shuffle=True),
        num_epoch=10, learning_rate=0.1, ctx=mx.cpu())
    clean_acc = (model.predict(mx.io.NDArrayIter(X, y, batch_size=64))
                 .argmax(axis=1) == y).mean()

    # executor with a gradient on the DATA input
    exe = net.simple_bind(mx.cpu(), grad_req={"data": "write"},
                          data=(n, d))
    for k, v in model.arg_params.items():
        exe.arg_dict[k][:] = v
    exe.arg_dict["data"][:] = X
    exe.arg_dict["softmax_label"][:] = y
    exe.forward(is_train=True)
    exe.backward()
    grad_sign = np.sign(exe.grad_dict["data"].asnumpy())
    X_adv = (X + epsilon * grad_sign).astype(np.float32)

    adv_acc = (model.predict(mx.io.NDArrayIter(X_adv, y, batch_size=64))
               .argmax(axis=1) == y).mean()
    print("clean accuracy: %.3f  adversarial accuracy: %.3f (eps=%.2f)"
          % (clean_acc, adv_acc, epsilon))
    assert clean_acc > 0.9, clean_acc
    assert adv_acc < clean_acc - 0.1, (clean_acc, adv_acc)
    print("FGSM OK")


if __name__ == "__main__":
    main()
