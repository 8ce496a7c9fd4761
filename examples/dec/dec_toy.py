#!/usr/bin/env python
"""Deep Embedded Clustering (reference example/dec): pretrain an
autoencoder, k-means the embeddings, then jointly refine encoder +
centroids by minimizing KL(P || Q) of the student-t soft assignments —
the whole DEC objective built from symbols (pow/broadcast/MakeLoss),
with the centroids as a trainable Variable.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx

K = 3       # clusters
EMB = 2     # embedding dim
D = 16      # input dim


def encoder(data):
    h = mx.sym.FullyConnected(data, num_hidden=32, name="enc1")
    h = mx.sym.Activation(h, act_type="relu")
    return mx.sym.FullyConnected(h, num_hidden=EMB, name="emb")


def soft_assignment(z, centroids, n):
    """Student-t q_ij over (n, K): 1/(1+||z_i - mu_j||^2) normalized."""
    zb = mx.sym.Reshape(z, shape=(n, 1, EMB))
    zb = mx.sym.broadcast_axis(zb, axis=1, size=K)          # (n,K,E)
    cb = mx.sym.Reshape(centroids, shape=(1, K, EMB))
    cb = mx.sym.broadcast_axis(cb, axis=0, size=n)          # (n,K,E)
    d2 = mx.sym.sum(mx.sym.square(zb - cb), axis=2)         # (n,K)
    inv = 1.0 / (1.0 + d2)
    return inv / mx.sym.Reshape(mx.sym.sum(inv, axis=1), shape=(n, 1))


def main(seed=0, n=300):
    rng = np.random.RandomState(seed)
    # 3 gaussian clusters living on a low-dim manifold in 16-d
    labels = rng.randint(0, K, n)
    centers2d = np.array([[3, 0], [-3, 0], [0, 3]], np.float32)
    latent = centers2d[labels] + rng.randn(n, 2) * 0.4
    lift = rng.randn(2, D).astype(np.float32)
    X = np.tanh(latent @ lift).astype(np.float32)

    # --- 1. pretrain the autoencoder -----------------------------------
    data = mx.sym.Variable("data")
    z = encoder(data)
    dec = mx.sym.FullyConnected(z, num_hidden=32, name="dec0")
    dec = mx.sym.Activation(dec, act_type="relu")
    dec = mx.sym.FullyConnected(dec, num_hidden=D, name="dec1")
    recon = mx.sym.LinearRegressionOutput(
        data=dec, label=mx.sym.Variable("recon_label"), name="recon")
    ae = recon.simple_bind(mx.cpu(), data=(n, D), recon_label=(n, D))
    init = mx.init.Xavier()
    for name, arr in ae.arg_dict.items():
        if name not in ("data", "recon_label"):
            init(name, arr)
    up = mx.optimizer.get_updater(
        mx.optimizer.create("adam", learning_rate=5e-3))
    ae.arg_dict["data"][:] = X
    ae.arg_dict["recon_label"][:] = X
    for step in range(1200):
        ae.forward(is_train=True)
        ae.backward()
        for i, nm in enumerate(recon.list_arguments()):
            if nm in ("data", "recon_label"):
                continue
            up(i, ae.grad_dict[nm], ae.arg_dict[nm])

    # --- 2. k-means init of centroids on the embeddings ----------------
    emb_exe = z.simple_bind(mx.cpu(), data=(n, D))
    emb_exe.arg_dict["data"][:] = X
    for nm in ("enc1_weight", "enc1_bias", "emb_weight", "emb_bias"):
        emb_exe.arg_dict[nm][:] = ae.arg_dict[nm].asnumpy()
    Z = emb_exe.forward()[0].asnumpy()

    def kmeans_once(init_idx):
        m = Z[init_idx].copy()
        for _ in range(25):
            a = ((Z[:, None, :] - m[None]) ** 2).sum(2).argmin(1)
            for j in range(K):
                if (a == j).any():
                    m[j] = Z[a == j].mean(axis=0)
        inertia = ((Z - m[a]) ** 2).sum()
        return m, inertia

    # multi-restart: a single draw can seed two centroids in one cluster
    mu, best = None, np.inf
    for _ in range(5):
        m, inertia = kmeans_once(rng.choice(n, K, replace=False))
        if inertia < best:
            mu, best = m, inertia

    # --- 3. DEC refinement: minimize KL(P||Q), centroids trainable -----
    q = soft_assignment(encoder(data), mx.sym.Variable("centroids"), n)
    p = mx.sym.Variable("target_p")
    kl = mx.sym.MakeLoss(mx.sym.sum(p * (mx.sym.log(p) - mx.sym.log(q))))
    dec_exe = kl.simple_bind(mx.cpu(), data=(n, D), centroids=(K, EMB),
                             target_p=(n, K),
                             grad_req={nm: "write" for nm
                                       in kl.list_arguments()
                                       if nm not in ("data", "target_p")})
    for nm in ("enc1_weight", "enc1_bias", "emb_weight", "emb_bias"):
        dec_exe.arg_dict[nm][:] = ae.arg_dict[nm].asnumpy()
    dec_exe.arg_dict["centroids"][:] = mu
    dec_exe.arg_dict["data"][:] = X
    up2 = mx.optimizer.get_updater(
        mx.optimizer.create("adam", learning_rate=2e-3))
    for it in range(30):
        # current Q -> sharpened target P (DEC eq. 3), updated per epoch
        # (computed host-side from the current embedding + centroids)
        Zc = dec_exe.arg_dict["centroids"].asnumpy()
        for nm in ("enc1_weight", "enc1_bias", "emb_weight", "emb_bias"):
            emb_exe.arg_dict[nm][:] = dec_exe.arg_dict[nm].asnumpy()
        Z = emb_exe.forward()[0].asnumpy()
        inv = 1.0 / (1.0 + ((Z[:, None] - Zc[None]) ** 2).sum(2))
        Q = inv / inv.sum(1, keepdims=True)
        W = Q ** 2 / Q.sum(0, keepdims=True)
        P = W / W.sum(1, keepdims=True)
        dec_exe.arg_dict["target_p"][:] = P.astype(np.float32)
        for _ in range(10):
            dec_exe.forward(is_train=True)
            dec_exe.backward()
            for i, nm in enumerate(kl.list_arguments()):
                if nm in ("data", "target_p"):
                    continue
                up2(100 + i, dec_exe.grad_dict[nm], dec_exe.arg_dict[nm])

    # --- evaluate: cluster purity under best label permutation ---------
    assign = Q.argmax(1)
    from itertools import permutations

    acc = max((assign == np.array([perm[l] for l in labels])).mean()
              for perm in permutations(range(K)))
    print("DEC cluster accuracy (best permutation): %.3f" % acc)
    assert acc > 0.9, acc
    print("DEC OK")


if __name__ == "__main__":
    main()
