#!/usr/bin/env python
"""Training memory cost (reference example/memcost + memonger):
quantify what ``MXNET_BACKWARD_DO_MIRROR`` buys on a deep MLP.

The mirror flag routes graph evaluation through segmented
rematerialization (``make_graph_eval(remat=True)``): the topo order is
split into ~sqrt(N) ``jax.checkpoint`` segments, so the backward pass
stores only segment-boundary activations and recomputes inside each
segment — the reference memonger's sqrt schedule. The measured quantity
is the byte size of the residuals the vjp must hold between forward and
backward (the activation memory remat exists to shrink); the price is
one extra forward's worth of FLOPs, reported via XLA's cost analysis.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import jax

import mxnet_tpu as mx
from mxnet_tpu.executor import make_graph_eval

DEPTH = 24
WIDTH = 256
BATCH = 256


def build():
    net = mx.sym.Variable("data")
    for i in range(DEPTH):
        net = mx.sym.FullyConnected(net, num_hidden=WIDTH,
                                    name="fc%d" % i)
        net = mx.sym.Activation(net, act_type="tanh")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="cls")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def measure(remat: bool):
    """(residual bytes held between fwd and bwd, train-step flops)."""
    net = build()
    ev, _ = make_graph_eval(net, remat=remat)
    arg_shapes, _, _ = net.infer_shape(data=(BATCH, WIDTH))
    rng = np.random.RandomState(0)
    args = [rng.randn(*s).astype(np.float32) * 0.05 for s in arg_shapes]
    key = jax.random.PRNGKey(0)

    def f(args):
        outs, _aux = ev(args, [], key, True)
        return outs[0]

    _, vjp = jax.vjp(f, args)
    res_bytes = sum(l.nbytes for l in jax.tree_util.tree_leaves(vjp)
                    if hasattr(l, "nbytes"))
    # the vjp also holds the args themselves (params + data are matmul
    # backward operands whether or not remat is on) — a constant floor
    # that is not activation memory; subtract it so the ratio measures
    # what remat can actually shrink
    arg_bytes = sum(a.nbytes for a in args)
    res_bytes = max(0, res_bytes - arg_bytes)

    # recompute cost: count matmuls in the emitted (pre-optimization)
    # backward program — remat re-runs each segment's forward inside the
    # backward, guarded by optimization_barrier so the compiler must
    # honor it (a backend MAY still trade it back; CPU XLA does)
    txt = jax.jit(jax.grad(lambda a: f(a).sum())).lower(args).as_text()
    dots = txt.count("stablehlo.dot")
    barriers = txt.count("optimization_barrier")
    return res_bytes, dots, barriers


def main():
    plain_bytes, plain_dots, _ = measure(False)
    remat_bytes, remat_dots, barriers = measure(True)
    mem_ratio = remat_bytes / plain_bytes
    dot_ratio = remat_dots / plain_dots
    print("%d-layer MLP, batch %d: stored activations %.1f -> %.1f MiB "
          "(%.2fx); emitted matmuls %d -> %d (%.2fx recompute), "
          "%d segment barriers"
          % (DEPTH, BATCH, plain_bytes / 2**20, remat_bytes / 2**20,
             mem_ratio, plain_dots, remat_dots, dot_ratio, barriers))
    # sqrt-schedule remat: stored activations shrink by a lot, at the
    # price of at most one extra forward of recompute
    assert mem_ratio < 0.3, mem_ratio
    assert plain_dots < remat_dots <= 2 * plain_dots, (plain_dots,
                                                       remat_dots)
    assert barriers > 0
    print("memcost OK")


if __name__ == "__main__":
    main()
