"""Predict-with-a-pretrained-model walkthrough (reference
example/notebooks/predict-with-pretrained-model.ipynb): load a
checkpointed model by (prefix, epoch), run batch prediction, read
top-k classes, and extract an INTERNAL feature layer by rebinding the
symbol's internals — the notebook's feature-extraction trick.

Zero-egress stand-in for the downloaded Inception checkpoint: a small
convnet trained briefly on synthetic blobs, saved, then reloaded.
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx

rng = np.random.RandomState(0)
n = 192
X = rng.rand(n, 1, 12, 12).astype(np.float32) * 0.3
y = rng.randint(0, 3, n).astype(np.float32)
for i in range(n):                      # class-dependent blob position
    c = int(y[i])
    X[i, 0, 2 + 3 * c:5 + 3 * c, 4:8] += 2.0

data = mx.sym.Variable("data")
net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), name="c1")
net = mx.sym.Activation(net, act_type="relu", name="relu1")
net = mx.sym.Flatten(net, name="flat")
net = mx.sym.FullyConnected(net, num_hidden=16, name="feat")
net = mx.sym.Activation(net, act_type="relu", name="featact")
net = mx.sym.FullyConnected(net, num_hidden=3, name="fc")
net = mx.sym.SoftmaxOutput(net, name="softmax")

model = mx.model.FeedForward(net, ctx=mx.cpu(), num_epoch=20,
                             learning_rate=0.05, numpy_batch_size=32,
                             initializer=mx.initializer.Xavier())
model.fit(X=X, y=y)

prefix = os.path.join(tempfile.mkdtemp(prefix="nb_pretrained_"), "m")
model.save(prefix, 20)

# --- the notebook's flow starts here: load by prefix/epoch, predict ---
loaded = mx.model.FeedForward.load(prefix, 20)
probs = loaded.predict(X[:32])
assert probs.shape == (32, 3)
topk = probs.argsort(axis=1)[:, ::-1][:, :2]      # top-2 classes
acc = float((probs.argmax(axis=1) == y[:32]).mean())
print("top-1 accuracy on train slice: %.3f" % acc)
assert acc > 0.9, acc
assert all(topk[i, 0] == probs[i].argmax() for i in range(32))

# --- feature extraction: rebind an internal layer as the output ---
internals = loaded.symbol.get_internals()
feat_sym = internals["featact_output"]
feat = mx.model.FeedForward(feat_sym, ctx=mx.cpu(),
                            arg_params=loaded.arg_params,
                            aux_params=loaded.aux_params)
feats = feat.predict(X[:8])
assert feats.shape == (8, 16)
assert np.abs(feats).sum() > 0
print("predict pretrained OK")
