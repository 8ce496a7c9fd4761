#!/usr/bin/env python
"""Noise-contrastive estimation (reference example/nce-loss): train a
large-vocabulary next-token scorer without a full softmax — score the
true class against k sampled noise classes with logistic loss, built
from Embedding + batch_dot like the reference's nce.py.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx

VOCAB = 200
EMBED = 24
K = 8  # noise samples per example


def build_net():
    data = mx.sym.Variable("data")            # (N,) context token
    cand = mx.sym.Variable("cand")            # (N, 1+K) true + noise ids
    in_vec = mx.sym.Embedding(data, input_dim=VOCAB, output_dim=EMBED,
                              name="in_embed")           # (N, E)
    out_vec = mx.sym.Embedding(cand, input_dim=VOCAB, output_dim=EMBED,
                               name="out_embed")         # (N, 1+K, E)
    q = mx.sym.Reshape(in_vec, shape=(-1, EMBED, 1))     # (N, E, 1)
    logits = mx.sym.batch_dot(out_vec, q)                # (N, 1+K, 1)
    logits = mx.sym.Reshape(logits, shape=(-1, 1 + K))
    return mx.sym.LogisticRegressionOutput(
        data=logits, label=mx.sym.Variable("label"), name="nce")


def main(seed=0, epochs=12, batch=64):
    rng = np.random.RandomState(seed)
    # deterministic bigram structure: next = (ctx * 7 + 3) % VOCAB
    n = 1024
    ctx_tok = rng.randint(0, VOCAB, n)
    true_next = (ctx_tok * 7 + 3) % VOCAB
    net = build_net()
    exe = net.simple_bind(mx.cpu(), data=(batch,), cand=(batch, 1 + K),
                          label=(batch, 1 + K))
    init = mx.init.Uniform(0.1)
    for name, arr in exe.arg_dict.items():
        if name.endswith("weight"):
            init(name, arr)
    updater = mx.optimizer.get_updater(
        mx.optimizer.create("adam", learning_rate=1e-2))
    skip = {"data", "cand", "label"}
    label = np.zeros((batch, 1 + K), np.float32)
    label[:, 0] = 1.0

    for epoch in range(epochs):
        for i in range(0, n - batch + 1, batch):
            c = ctx_tok[i:i + batch]
            t = true_next[i:i + batch]
            noise = rng.randint(0, VOCAB, (batch, K))
            cand = np.concatenate([t[:, None], noise], axis=1)
            exe.arg_dict["data"][:] = c.astype(np.float32)
            exe.arg_dict["cand"][:] = cand.astype(np.float32)
            exe.arg_dict["label"][:] = label
            exe.forward(is_train=True)
            exe.backward()
            for j, name in enumerate(net.list_arguments()):
                if name in skip:
                    continue
                updater(j, exe.grad_dict[name], exe.arg_dict[name])

    # evaluation: full-vocabulary argmax using the learned embeddings
    in_w = exe.arg_dict["in_embed_weight"].asnumpy()
    out_w = exe.arg_dict["out_embed_weight"].asnumpy()
    test_ctx = rng.randint(0, VOCAB, 256)
    scores = in_w[test_ctx] @ out_w.T                    # (256, VOCAB)
    pred = scores.argmax(axis=1)
    acc = (pred == (test_ctx * 7 + 3) % VOCAB).mean()
    print("full-softmax top-1 from NCE-trained embeddings: %.3f" % acc)
    assert acc > 0.6, acc
    print("NCE OK")


if __name__ == "__main__":
    main()
