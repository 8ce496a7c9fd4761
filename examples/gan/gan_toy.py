#!/usr/bin/env python
"""Toy GAN (reference example/gan, shrunk to a 2-D mixture): generator
and discriminator as two executors trained adversarially with
LogisticRegressionOutput, the two-executor update dance of the
reference's dcgan.py.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx


def generator(z_dim):
    z = mx.sym.Variable("z")
    g = mx.sym.FullyConnected(z, num_hidden=32, name="g1")
    g = mx.sym.Activation(g, act_type="relu")
    g = mx.sym.FullyConnected(g, num_hidden=2, name="g2")
    return g


def discriminator():
    x = mx.sym.Variable("x")
    d = mx.sym.FullyConnected(x, num_hidden=32, name="d1")
    d = mx.sym.Activation(d, act_type="tanh")
    d = mx.sym.FullyConnected(d, num_hidden=1, name="d2")
    return mx.sym.LogisticRegressionOutput(
        data=d, label=mx.sym.Variable("label"), name="dout")


def _init(exe, skip, seed):
    init = mx.init.Xavier()
    for name, arr in exe.arg_dict.items():
        if name not in skip:
            init(name, arr)


def _sgd_step(sym, exe, skip, updater, base_index=0):
    for i, name in enumerate(sym.list_arguments()):
        if name in skip:
            continue
        updater(base_index + i, exe.grad_dict[name], exe.arg_dict[name])


def real_batch(rng, n):
    # ring of 4 gaussians
    centers = np.array([[2, 0], [-2, 0], [0, 2], [0, -2]], np.float32)
    idx = rng.randint(0, 4, n)
    return centers[idx] + rng.randn(n, 2).astype(np.float32) * 0.2


def main(seed=0, steps=1000, batch=64, z_dim=8):
    rng = np.random.RandomState(seed)
    g_sym = generator(z_dim)
    d_sym = discriminator()

    g_exe = g_sym.simple_bind(mx.cpu(), z=(batch, z_dim))
    d_reqs = {n: "write" for n in d_sym.list_arguments()}
    d_reqs["label"] = "null"          # no gradient for the target
    d_exe = d_sym.simple_bind(mx.cpu(), grad_req=d_reqs,
                              x=(batch, 2), label=(batch, 1))
    _init(g_exe, {"z"}, seed)
    _init(d_exe, {"x", "label"}, seed + 1)
    g_up = mx.optimizer.get_updater(
        mx.optimizer.create("adam", learning_rate=1e-2))
    d_up = mx.optimizer.get_updater(
        mx.optimizer.create("adam", learning_rate=2e-3))

    ones = np.ones((batch, 1), np.float32)
    zeros = np.zeros((batch, 1), np.float32)
    for step in range(steps):
        # --- discriminator on real
        d_exe.arg_dict["x"][:] = real_batch(rng, batch)
        d_exe.arg_dict["label"][:] = ones
        d_exe.forward(is_train=True)
        d_exe.backward()
        _sgd_step(d_sym, d_exe, {"x", "label"}, d_up)
        # --- discriminator on fake
        g_exe.arg_dict["z"][:] = rng.randn(batch, z_dim).astype(np.float32)
        g_exe.forward(is_train=True)
        fake = g_exe.outputs[0].asnumpy()
        d_exe.arg_dict["x"][:] = fake
        d_exe.arg_dict["label"][:] = zeros
        d_exe.forward(is_train=True)
        d_exe.backward()
        _sgd_step(d_sym, d_exe, {"x", "label"}, d_up)
        # --- generator: push D(fake) toward "real", gradient flows
        #     through D's input gradient into G
        d_exe.arg_dict["label"][:] = ones
        d_exe.forward(is_train=True)
        d_exe.backward()
        g_exe.backward([mx.nd.array(d_exe.grad_dict["x"].asnumpy())])
        _sgd_step(g_sym, g_exe, {"z"}, g_up, base_index=100)

    # fakes should land near the 4 modes: mean distance to the nearest
    # center well under the prior's
    g_exe.arg_dict["z"][:] = rng.randn(batch, z_dim).astype(np.float32)
    fake = g_exe.forward()[0].asnumpy()
    centers = np.array([[2, 0], [-2, 0], [0, 2], [0, -2]], np.float32)
    dists = np.linalg.norm(fake[:, None, :] - centers[None], axis=2).min(1)
    print("mean distance of fakes to nearest mode: %.3f" % dists.mean())
    assert dists.mean() < 1.2, dists.mean()
    print("GAN OK")


if __name__ == "__main__":
    main()
