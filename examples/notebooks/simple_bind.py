"""simple_bind walkthrough (reference example/notebooks/simple_bind.ipynb):
the LOW-LEVEL training loop — simple_bind an MLP, initialize arg arrays
by hand, run forward/backward yourself, and apply SGD directly to the
executor's arrays; no Module/FeedForward anywhere.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx

rng = np.random.RandomState(0)
n = 256
X = rng.randn(n, 16).astype(np.float32)
y = (X[:, :4].sum(axis=1) > 0).astype(np.float32)

net = mx.sym.Variable("data")
net = mx.sym.FullyConnected(net, num_hidden=32, name="fc1")
net = mx.sym.Activation(net, act_type="relu", name="act1")
net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
net = mx.sym.SoftmaxOutput(net, name="softmax")

batch = 32
ex = net.simple_bind(ctx=mx.cpu(), data=(batch, 16), grad_req="write")

# hand initialization, notebook-style
for name, arr in ex.arg_dict.items():
    if name.endswith("weight"):
        arr[:] = rng.uniform(-0.07, 0.07, arr.shape).astype(np.float32)
    elif name.endswith("bias"):
        arr[:] = 0

lr = 0.2
for epoch in range(12):
    correct = 0
    for start in range(0, n, batch):
        ex.arg_dict["data"][:] = X[start:start + batch]
        ex.arg_dict["softmax_label"][:] = y[start:start + batch]
        ex.forward(is_train=True)
        ex.backward()
        for name, grad in ex.grad_dict.items():
            if grad is None or name in ("data", "softmax_label"):
                continue
            ex.arg_dict[name][:] = ex.arg_dict[name] - (lr / batch) * grad
        pred = ex.outputs[0].asnumpy().argmax(axis=1)
        correct += int((pred == y[start:start + batch]).sum())
    acc = correct / n
final = acc
print("final accuracy %.3f" % final)
assert final > 0.95, final
print("simple bind OK")
