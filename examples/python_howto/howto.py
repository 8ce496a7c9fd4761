"""Python how-to walkthrough (reference example/python-howto/):
multiple_outputs.py (Group + bind exposes internal layers),
data_iter.py (custom DataIter protocol), monitor_weights.py
(Monitor with a norm stat installed through fit) — as one asserting
script instead of notebooks.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx

# ---- multiple outputs: group an internal layer with the head --------
net = mx.sym.Variable("data")
fc1 = mx.sym.FullyConnected(data=net, name="fc1", num_hidden=16)
relu = mx.sym.Activation(data=fc1, name="relu1", act_type="relu")
fc2 = mx.sym.FullyConnected(data=relu, name="fc2", num_hidden=4)
out = mx.sym.SoftmaxOutput(data=fc2, name="softmax")
group = mx.sym.Group([fc1, out])
assert group.list_outputs() == ["fc1_output", "softmax_output"]
ex = group.simple_bind(mx.cpu(), data=(2, 8))
ex.arg_dict["data"][:] = np.random.RandomState(0).randn(2, 8)
outs = ex.forward()
assert outs[0].shape == (2, 16)          # the internal fc1 value
assert outs[1].shape == (2, 4)
np.testing.assert_allclose(outs[1].asnumpy().sum(axis=1), np.ones(2),
                           rtol=1e-5)

# ---- custom data iter (data_iter.py protocol) -----------------------
class SimpleIter(mx.io.DataIter):
    def __init__(self, n_batches=8, batch=16):
        super().__init__()
        self.batch_size = batch
        self.n = n_batches
        self.i = -1
        self.rng = np.random.RandomState(1)

    @property
    def provide_data(self):
        return [mx.io.DataDesc("data", (self.batch_size, 8))]

    @property
    def provide_label(self):
        return [mx.io.DataDesc("softmax_label", (self.batch_size,))]

    def reset(self):
        self.i = -1

    def iter_next(self):
        self.i += 1
        return self.i < self.n

    def getdata(self):
        x = self.rng.randn(self.batch_size, 8).astype(np.float32)
        self._y = (x[:, 0] > 0).astype(np.float32)
        x[:, 1] += self._y * 2
        return [mx.nd.array(x)]

    def getlabel(self):
        return [mx.nd.array(self._y)]


# ---- monitor_weights.py: norm stat per batch through fit ------------
stats = []


def norm_stat(d):
    return mx.nd.norm(d) / np.sqrt(d.size)


mon = mx.monitor.Monitor(1, norm_stat)
mod = mx.mod.Module(out, context=mx.cpu())
mod.fit(SimpleIter(), num_epoch=2, monitor=mon,
        optimizer_params={"learning_rate": 0.1})
print("python howto OK")
