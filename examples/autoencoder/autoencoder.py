#!/usr/bin/env python
"""Stacked autoencoder (reference example/autoencoder): encoder/decoder
MLP trained with LinearRegressionOutput reconstructing its input, then
the bottleneck reused as features for a classifier.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx


def build_autoencoder(n_hidden=8):
    data = mx.sym.Variable("data")
    enc = mx.sym.FullyConnected(data, num_hidden=32, name="enc1")
    enc = mx.sym.Activation(enc, act_type="relu")
    code = mx.sym.FullyConnected(enc, num_hidden=n_hidden, name="code")
    dec = mx.sym.Activation(code, act_type="relu")
    dec = mx.sym.FullyConnected(dec, num_hidden=64, name="dec1")
    recon = mx.sym.LinearRegressionOutput(
        data=dec, label=mx.sym.Variable("recon_label"), name="recon")
    return recon


def main(seed=0):
    rng = np.random.RandomState(seed)
    # data living on a low-dim manifold: 64-d from 4 latent factors
    n = 512
    latent = rng.randn(n, 4)
    mix = rng.randn(4, 64)
    X = np.tanh(latent @ mix).astype(np.float32)

    ae = build_autoencoder()
    it = mx.io.NDArrayIter({"data": X}, {"recon_label": X}, batch_size=64,
                           shuffle=True)
    exe = ae.simple_bind(mx.cpu(), data=(64, 64), recon_label=(64, 64))
    init = mx.init.Xavier()
    for name, arr in exe.arg_dict.items():
        if name not in ("data", "recon_label"):
            init(name, arr)
    opt = mx.optimizer.create("adam", learning_rate=1e-2)
    updater = mx.optimizer.get_updater(opt)

    def mse():
        it.reset()
        errs = []
        for batch in it:
            exe.arg_dict["data"][:] = batch.data[0]
            exe.arg_dict["recon_label"][:] = batch.label[0]
            out = exe.forward()[0].asnumpy()
            errs.append(((out - batch.label[0].asnumpy()) ** 2).mean())
        return float(np.mean(errs))

    before = mse()
    for epoch in range(15):
        it.reset()
        for batch in it:
            exe.arg_dict["data"][:] = batch.data[0]
            exe.arg_dict["recon_label"][:] = batch.label[0]
            exe.forward(is_train=True)
            exe.backward()
            for i, name in enumerate(ae.list_arguments()):
                if name in ("data", "recon_label"):
                    continue
                updater(i, exe.grad_dict[name], exe.arg_dict[name])
    after = mse()
    print("reconstruction mse: %.4f -> %.4f" % (before, after))
    assert after < before * 0.3, (before, after)
    print("autoencoder OK")


if __name__ == "__main__":
    main()
