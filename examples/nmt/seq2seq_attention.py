#!/usr/bin/env python
"""Seq2seq with attention (reference example/nmt): encoder LSTM via
the fused RNN op, per-step decoder with Luong dot attention built from
batch_dot + SoftmaxActivation, trained to emit the reversed input
sequence — the translation-toy the reference's NMT example reduced to.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.ops.seq import rnn_param_size

VOCAB = 10
SEQ = 6
EMBED = 16
HIDDEN = 32


def build(batch):
    src = mx.sym.Variable("src")                    # (T, N) ids
    emb = mx.sym.Embedding(src, input_dim=VOCAB, output_dim=EMBED,
                           name="src_embed")        # (T, N, E)
    enc = mx.sym.RNN(data=emb, parameters=mx.sym.Variable("enc_params"),
                     state=mx.sym.Variable("enc_state"),
                     state_cell=mx.sym.Variable("enc_cell"),
                     state_size=HIDDEN, num_layers=1, mode="lstm",
                     name="encoder")                # (T, N, H)
    # decoder: unrolled steps; input = previous target token (teacher
    # forcing), context = Luong dot attention over encoder states
    enc_nth = mx.sym.SwapAxis(enc, dim1=0, dim2=1)  # (N, T, H)
    tgt_in = mx.sym.Variable("tgt_in")              # (T, N) shifted ids
    tgt_emb = mx.sym.Embedding(tgt_in, input_dim=VOCAB, output_dim=EMBED,
                               name="tgt_embed")    # (T, N, E)
    steps = mx.sym.SliceChannel(tgt_emb, num_outputs=SEQ, axis=0,
                                squeeze_axis=True)  # SEQ x (N, E)

    # decoder cell weights shared across steps (one Variable set)
    w_ih = mx.sym.Variable("dec_ih_weight")
    b_ih = mx.sym.Variable("dec_ih_bias")
    w_hh = mx.sym.Variable("dec_hh_weight")
    b_hh = mx.sym.Variable("dec_hh_bias")
    w_out = mx.sym.Variable("out_weight")
    b_out = mx.sym.Variable("out_bias")

    h = mx.sym.Variable("dec_h0")                   # (N, H) zeros
    logits = []
    for t in range(SEQ):
        x_t = steps[t]                              # (N, E)
        gx = mx.sym.FullyConnected(data=x_t, weight=w_ih, bias=b_ih,
                                   num_hidden=HIDDEN,
                                   name="dec_ih%d" % t)
        gh = mx.sym.FullyConnected(data=h, weight=w_hh, bias=b_hh,
                                   num_hidden=HIDDEN,
                                   name="dec_hh%d" % t)
        h = mx.sym.Activation(gx + gh, act_type="tanh")
        # Luong dot attention: scores (N, T) = enc_nth @ h
        hq = mx.sym.Reshape(h, shape=(batch, HIDDEN, 1))
        scores = mx.sym.batch_dot(enc_nth, hq)       # (N, T, 1)
        scores = mx.sym.Reshape(scores, shape=(batch, SEQ))
        alpha = mx.sym.SoftmaxActivation(scores)     # (N, T)
        alpha3 = mx.sym.Reshape(alpha, shape=(batch, 1, SEQ))
        ctx_vec = mx.sym.batch_dot(alpha3, enc_nth)  # (N, 1, H)
        ctx_vec = mx.sym.Reshape(ctx_vec, shape=(batch, HIDDEN))
        feat = mx.sym.Concat(h, ctx_vec, dim=1)      # (N, 2H)
        logits.append(mx.sym.FullyConnected(
            data=feat, weight=w_out, bias=b_out, num_hidden=VOCAB,
            name="out%d" % t))
    out = mx.sym.Concat(*[mx.sym.Reshape(l, shape=(1, batch, VOCAB))
                          for l in logits], dim=0)  # (T, N, V)
    out = mx.sym.Reshape(out, shape=(SEQ * batch, VOCAB))
    return mx.sym.SoftmaxOutput(out, name="softmax")


def main(seed=0, batch=32, epochs=30):
    rng = np.random.RandomState(seed)
    net = build(batch)
    psize = rnn_param_size(1, EMBED, HIDDEN, False, "lstm")
    exe = net.simple_bind(
        mx.cpu(), src=(SEQ, batch), tgt_in=(SEQ, batch),
        enc_params=(psize,), enc_state=(1, batch, HIDDEN),
        enc_cell=(1, batch, HIDDEN), dec_h0=(batch, HIDDEN),
        softmax_label=(SEQ * batch,))
    init = mx.init.Xavier()
    skip = {"src", "tgt_in", "softmax_label", "enc_state", "enc_cell",
            "dec_h0"}
    for name, arr in exe.arg_dict.items():
        if name not in skip:
            if name.endswith("_bias"):
                arr[:] = np.zeros(arr.shape, np.float32)
            else:
                init(name if name.endswith("weight") else name + "_weight",
                     arr)
    updater = mx.optimizer.get_updater(
        mx.optimizer.create("adam", learning_rate=5e-3))

    def make_batch():
        s = rng.randint(1, VOCAB, (SEQ, batch))
        tgt = s[::-1]                                # reverse task
        tgt_in = np.vstack([np.zeros((1, batch), int), tgt[:-1]])
        return (s.astype(np.float32), tgt_in.astype(np.float32),
                tgt.reshape(-1).astype(np.float32))

    for epoch in range(epochs):
        correct = total = 0
        for _ in range(16):
            s, t_in, t_out = make_batch()
            exe.arg_dict["src"][:] = s
            exe.arg_dict["tgt_in"][:] = t_in
            exe.arg_dict["softmax_label"][:] = t_out
            exe.forward(is_train=True)
            exe.backward()
            for i, nm in enumerate(net.list_arguments()):
                if nm in skip:
                    continue
                updater(i, exe.grad_dict[nm], exe.arg_dict[nm])
            pred = exe.outputs[0].asnumpy().argmax(axis=1)
            correct += (pred == t_out).sum()
            total += t_out.size
    acc = correct / total
    print("teacher-forced token accuracy (reverse task): %.3f" % acc)
    assert acc > 0.9, acc
    print("NMT OK")


if __name__ == "__main__":
    main()
