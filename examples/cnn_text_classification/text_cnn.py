#!/usr/bin/env python
"""CNN text classification (reference example/cnn_text_classification):
embedding -> parallel conv filters over the token axis -> max-over-time
pooling -> concat -> dense, Kim-2014 style, on a synthetic
phrase-detection task.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx

SEQ_LEN = 20
VOCAB = 50
EMBED = 16


def build_net():
    data = mx.sym.Variable("data")                       # (N, T)
    embed = mx.sym.Embedding(data, input_dim=VOCAB, output_dim=EMBED,
                             name="embed")               # (N, T, E)
    x = mx.sym.Reshape(embed, shape=(-1, 1, SEQ_LEN, EMBED))
    pooled = []
    for k in (3, 4, 5):
        c = mx.sym.Convolution(x, kernel=(k, EMBED), num_filter=8,
                               name="conv%d" % k)        # (N, 8, T-k+1, 1)
        c = mx.sym.Activation(c, act_type="relu")
        p = mx.sym.Pooling(c, kernel=(SEQ_LEN - k + 1, 1),
                           pool_type="max")              # (N, 8, 1, 1)
        pooled.append(mx.sym.Flatten(p))
    h = mx.sym.Concat(*pooled, dim=1)
    h = mx.sym.Dropout(h, p=0.2)
    fc = mx.sym.FullyConnected(h, num_hidden=2, name="cls")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def make_data(rng, n):
    """Positive iff the trigram (7, 8, 9) occurs."""
    X = rng.randint(10, VOCAB, (n, SEQ_LEN))
    y = rng.randint(0, 2, n)
    for i in np.where(y == 1)[0]:
        pos = rng.randint(0, SEQ_LEN - 3)
        X[i, pos:pos + 3] = [7, 8, 9]
    return X.astype(np.float32), y.astype(np.float32)


def main(seed=0):
    rng = np.random.RandomState(seed)
    Xtr, ytr = make_data(rng, 768)
    Xte, yte = make_data(rng, 256)
    net = build_net()
    model = mx.model.FeedForward.create(
        net, X=mx.io.NDArrayIter(Xtr, ytr, batch_size=64, shuffle=True),
        num_epoch=8, optimizer="adam", learning_rate=2e-3, ctx=mx.cpu())
    acc = (model.predict(mx.io.NDArrayIter(Xte, yte, batch_size=64))
           .argmax(axis=1) == yte).mean()
    print("test accuracy: %.3f" % acc)
    assert acc > 0.85, acc
    print("text CNN OK")


if __name__ == "__main__":
    main()
