#!/usr/bin/env python
"""Bidirectional-LSTM sort (reference example/bi-lstm-sort): read a
sequence of digits and emit them sorted, using the fused bidirectional
``sym.RNN`` (the reference unrolled cells by hand) with a per-timestep
softmax head.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.ops.seq import rnn_param_size

SEQ_LEN = 5
VOCAB = 8
HIDDEN = 32


def build_net(batch):
    data = mx.sym.Variable("data")          # (T, N) int ids
    embed = mx.sym.Embedding(data, input_dim=VOCAB, output_dim=16,
                             name="embed")  # (T, N, 16)
    rnn = mx.sym.RNN(data=embed,
                     parameters=mx.sym.Variable("rnn_params"),
                     state=mx.sym.Variable("rnn_state"),
                     state_cell=mx.sym.Variable("rnn_state_cell"),
                     state_size=HIDDEN, num_layers=1, mode="lstm",
                     bidirectional=True, name="birnn")  # (T, N, 2H)
    flat = mx.sym.Reshape(rnn, shape=(batch * SEQ_LEN, 2 * HIDDEN))
    fc = mx.sym.FullyConnected(flat, num_hidden=VOCAB, name="cls")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def batches(rng, n, batch):
    X = rng.randint(0, VOCAB, (n, SEQ_LEN))
    Y = np.sort(X, axis=1)
    for i in range(0, n - batch + 1, batch):
        x = X[i:i + batch].T.astype(np.float32)          # (T, N)
        y = Y[i:i + batch].T.reshape(-1).astype(np.float32)
        yield x, y


def main(seed=0, epochs=12, batch=32):
    rng = np.random.RandomState(seed)
    net = build_net(batch)
    psize = rnn_param_size(1, 16, HIDDEN, True, "lstm")
    exe = net.simple_bind(mx.cpu(), data=(SEQ_LEN, batch),
                          rnn_params=(psize,),
                          rnn_state=(2, batch, HIDDEN),
                          rnn_state_cell=(2, batch, HIDDEN),
                          softmax_label=(SEQ_LEN * batch,))
    init = mx.init.Xavier()
    for name, arr in exe.arg_dict.items():
        if name.startswith(("embed", "cls", "rnn_params")):
            init(name if "params" not in name else "%s_weight" % name,
                 arr)
    updater = mx.optimizer.get_updater(
        mx.optimizer.create("adam", learning_rate=5e-3))
    skip = {"data", "softmax_label", "rnn_state", "rnn_state_cell"}

    for epoch in range(epochs):
        correct = total = 0
        for x, y in batches(rng, 512, batch):
            exe.arg_dict["data"][:] = x
            exe.arg_dict["softmax_label"][:] = y
            exe.forward(is_train=True)
            exe.backward()
            for i, name in enumerate(net.list_arguments()):
                if name in skip:
                    continue
                updater(i, exe.grad_dict[name], exe.arg_dict[name])
            pred = exe.outputs[0].asnumpy().argmax(axis=1)
            correct += (pred == y).sum()
            total += y.size
        acc = correct / total
    print("sorted-digit accuracy after %d epochs: %.3f" % (epochs, acc))
    assert acc > 0.7, acc
    print("bi-LSTM sort OK")


if __name__ == "__main__":
    main()
