#!/usr/bin/env python
"""Finetuning (reference docs/how_to/finetune + pretrained-model zoo
workflow): load a trained checkpoint, graft a new classifier head onto
the trunk via get_internals, seed the trunk from the checkpoint's
arg_params, and train the new head — matching-name weight reuse, the
exact mechanics the reference used for ImageNet-pretrained finetuning.
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx


def base_net(num_classes):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="trunk1")
    net = mx.sym.Activation(net, act_type="relu", name="trunk_relu")
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="head")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def make_task(rng, n, d, k, w):
    y = rng.randint(0, k, n).astype(np.float32)
    X = (rng.randn(n, d) + w[y.astype(int)]).astype(np.float32)
    return X, y


def main(seed=0):
    rng = np.random.RandomState(seed)
    d = 16
    # pretraining task: 4 classes on a shared feature basis
    basis = rng.randn(6, d) * 2.0
    Xa, ya = make_task(rng, 512, d, 4, basis[:4])
    model = mx.model.FeedForward.create(
        base_net(4), X=mx.io.NDArrayIter(Xa, ya, batch_size=64,
                                         shuffle=True),
        num_epoch=8, learning_rate=0.2, ctx=mx.cpu())
    prefix = os.path.join(tempfile.mkdtemp(), "pretrained")
    model.save(prefix, 8)

    # --- finetune: same trunk, NEW 2-way head, small target dataset ---
    Xb, yb = make_task(rng, 96, d, 2, basis[4:6])
    sym_loaded, arg_params, aux_params = mx.model.load_checkpoint(prefix, 8)
    trunk = sym_loaded.get_internals()["trunk_relu_output"]
    new_head = mx.sym.FullyConnected(trunk, num_hidden=2, name="newhead")
    new_net = mx.sym.SoftmaxOutput(new_head, name="softmax")

    # trunk weights come from the checkpoint (matching names); the new
    # head initializes fresh. allow_missing is the reference's finetune
    # switch for exactly this.
    ft = mx.mod.Module(new_net, context=mx.cpu())
    it = mx.io.NDArrayIter(Xb, yb, batch_size=32, shuffle=True)
    ft.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    ft.init_params(mx.init.Xavier(), arg_params=arg_params,
                   aux_params=aux_params, allow_missing=True)
    # verify the trunk really came from the checkpoint
    got = ft.get_params()[0]["trunk1_weight"].asnumpy()
    np.testing.assert_allclose(got, arg_params["trunk1_weight"].asnumpy())
    ft.fit(it, num_epoch=6, optimizer_params={"learning_rate": 0.1})
    acc = (ft.predict(mx.io.NDArrayIter(Xb, batch_size=32)).asnumpy()
           .argmax(axis=1) == yb).mean()

    # scratch baseline on the same small data
    scratch = mx.mod.Module(new_net, context=mx.cpu())
    it.reset()
    scratch.fit(it, num_epoch=6, optimizer_params={"learning_rate": 0.1})
    scratch_acc = (scratch.predict(mx.io.NDArrayIter(Xb, batch_size=32))
                   .asnumpy().argmax(axis=1) == yb).mean()
    print("finetuned acc: %.3f  from-scratch acc: %.3f" % (acc, scratch_acc))
    assert acc > 0.9, acc
    print("finetune OK")


if __name__ == "__main__":
    main()
