#!/usr/bin/env python
"""Bayesian methods via SGLD (reference example/bayesian-methods):
stochastic gradient Langevin dynamics samples the posterior of a
Bayesian linear regression — the optimizer IS the sampler. After
burn-in, the iterate distribution matches the analytic posterior
N((X'X + I)^-1 X'y, sigma^2 (X'X + I)^-1).
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx


def main(seed=0, n=256, d=4, sigma=0.5):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(d).astype(np.float32)
    X = rng.randn(n, d).astype(np.float32)
    yv = (X @ w_true + rng.randn(n) * sigma).astype(np.float32)

    # posterior of w under unit gaussian prior + gaussian likelihood
    prec = X.T @ X / sigma**2 + np.eye(d)
    cov = np.linalg.inv(prec)
    mean = cov @ X.T @ yv / sigma**2

    # loss = ||y - Xw||^2 / (2 sigma^2): its gradient is the negative
    # log-likelihood gradient; SGLD's wd term supplies the prior
    data = mx.sym.Variable("data")
    pred = mx.sym.FullyConnected(data, num_hidden=1, no_bias=True,
                                 name="w")
    net = mx.sym.LinearRegressionOutput(
        data=pred, label=mx.sym.Variable("label"), name="out")
    exe = net.simple_bind(mx.cpu(), data=(n, d), label=(n, 1))
    exe.arg_dict["data"][:] = X
    exe.arg_dict["label"][:] = yv.reshape(-1, 1)
    exe.arg_dict["w_weight"][:] = np.zeros((1, d), np.float32)

    # LinearRegressionOutput backward yields the summed gradient
    # X'(Xw - y); scaling by 1/sigma^2 makes it the negative
    # log-likelihood gradient, and wd=1 adds the unit-gaussian prior
    opt = mx.optimizer.create("sgld", learning_rate=2e-4, wd=1.0,
                              rescale_grad=1.0 / sigma**2)
    updater = mx.optimizer.get_updater(opt)

    samples = []
    for step in range(6000):
        exe.forward(is_train=True)
        exe.backward()
        updater(0, exe.grad_dict["w_weight"], exe.arg_dict["w_weight"])
        if step >= 2000 and step % 2 == 0:
            samples.append(exe.arg_dict["w_weight"].asnumpy().ravel())
    S = np.stack(samples)

    mean_err = np.abs(S.mean(axis=0) - mean).max()
    std_err = np.abs(S.std(axis=0) - np.sqrt(np.diag(cov))).max()
    print("posterior mean err %.4f  std err %.4f (post std ~%.3f)"
          % (mean_err, std_err, np.sqrt(np.diag(cov)).mean()))
    assert mean_err < 0.1, mean_err
    assert std_err < 0.05, std_err
    print("SGLD OK")


if __name__ == "__main__":
    main()
