#!/usr/bin/env python
"""Torch plugin (reference plugin/torch + example/torch): a PyTorch
nn.Module embedded as a graph op via the torch bridge, trained
end-to-end next to native ops.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx


def main(seed=0):
    try:
        import torch
        import torch.nn as nn
    except ImportError:
        print("torch not available; skipping")
        return

    from mxnet_tpu.plugins.torch_bridge import torch_module

    rng = np.random.RandomState(seed)
    n, d = 384, 16
    y = rng.randint(0, 2, n).astype(np.float32)
    X = (rng.randn(n, d) + y[:, None] * 1.5).astype(np.float32)

    # a torch block in the middle of an mx graph
    data = mx.sym.Variable("data")
    h = torch_module(lambda: nn.Sequential(nn.Linear(16, 32), nn.Tanh()),
                     data=data, name="torchblock",
                     infer_shape_fn=lambda s: (s[0][0], 32))
    out = mx.sym.FullyConnected(h, num_hidden=2, name="cls")
    out = mx.sym.SoftmaxOutput(out, name="softmax")

    model = mx.model.FeedForward.create(
        out, X=mx.io.NDArrayIter(X, y, batch_size=64, shuffle=True),
        num_epoch=6, learning_rate=0.2, ctx=mx.cpu())
    acc = (model.predict(mx.io.NDArrayIter(X, y, batch_size=64))
           .argmax(axis=1) == y).mean()
    print("accuracy with embedded torch block: %.3f" % acc)
    assert acc > 0.85, acc
    print("torch plugin OK")


if __name__ == "__main__":
    main()
