#!/usr/bin/env python
"""TPU-vs-CPU operator consistency sweep.

Reference analogue: ``tests/python/gpu/test_operator_gpu.py`` — the
reference validated its cuDNN/GPU kernels by binding every op on
``mx.gpu(0)`` and comparing against the CPU path via
``check_consistency``. This is the same tier against the real TPU
backend: for each representative op config, bind on ``mx.tpu(0)`` and
``mx.cpu(0)`` and require matching outputs and gradients.

Run it on the machine with the chip, as the one process that owns it
(`python tools/tpu_consistency.py`, through the chip tool); without an
accelerator it exits non-zero. Prints one PASS/FAIL line per case and a
final summary line `TPU_CONSISTENCY ok=N fail=M failed=...`.

The verdict is taken at the backend's DEFAULT matmul precision, because
that is what a float32 `Module.fit` runs. A case that fails is run once
more at ``highest`` precision and its line says how that went: PASS
there means the two backends lower the same function and differ by the
TPU's float32-as-bf16-MXU-passes policy (v5e, PR 21: Convolution 1.08e-3,
Deconvolution 1.11e-3, FullyConnected 1.16e-3, RNN_lstm 7.2e-3 against
the 1e-3 float32 tolerance; ROADMAP Speed 10e), FAIL there means a wrong
kernel. The second pass never changes the verdict or the exit code.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def cases(mx):
    """(name, symbol, shapes, grad_req) — the cuDNN-class ops first."""
    sym = mx.sym
    data = sym.Variable("data")
    out = []
    out.append(("Convolution", sym.Convolution(
        data, kernel=(3, 3), num_filter=8, pad=(1, 1), name="c"),
        {"data": (2, 3, 10, 10)}, "write"))
    out.append(("Deconvolution", sym.Deconvolution(
        data, kernel=(4, 4), stride=(2, 2), pad=(1, 1), num_filter=4,
        name="dc"), {"data": (2, 3, 8, 8)}, "write"))
    out.append(("Pooling_max", sym.Pooling(
        data, kernel=(2, 2), stride=(2, 2), pool_type="max"),
        {"data": (2, 3, 8, 8)}, "write"))
    out.append(("Pooling_avg", sym.Pooling(
        data, kernel=(2, 2), stride=(2, 2), pool_type="avg"),
        {"data": (2, 3, 8, 8)}, "write"))
    out.append(("BatchNorm", sym.BatchNorm(data, name="bn"),
                {"data": (4, 3, 6, 6)}, "write"))
    out.append(("FullyConnected", sym.FullyConnected(
        data, num_hidden=8, name="fc"), {"data": (4, 12)}, "write"))
    out.append(("Activation_tanh", sym.Activation(data, act_type="tanh"),
                {"data": (4, 12)}, "write"))
    out.append(("LeakyReLU", sym.LeakyReLU(data, act_type="leaky"),
                {"data": (4, 12)}, "write"))
    out.append(("SoftmaxActivation", sym.SoftmaxActivation(data),
                {"data": (4, 12)}, "write"))
    out.append(("LRN", sym.LRN(data, nsize=3), {"data": (2, 6, 5, 5)},
                "write"))
    # inference-only: train-mode dropout draws per-executor PRNG keys,
    # so outputs would differ by construction
    out.append(("Dropout_inference", sym.Dropout(data, p=0.5),
                {"data": (4, 12)}, "null"))
    # fused RNN (the cudnn_rnn analogue): multi-arg bind
    from mxnet_tpu.ops.seq import rnn_param_size

    psize = rnn_param_size(1, 6, 5, False, "lstm")
    rnn = sym.RNN(data=data, parameters=sym.Variable("p"),
                  state=sym.Variable("s"), state_cell=sym.Variable("c"),
                  state_size=5, num_layers=1, mode="lstm", name="rnn")
    out.append(("RNN_lstm", rnn,
                {"data": (3, 2, 6), "p": (psize,), "s": (1, 2, 5),
                 "c": (1, 2, 5)}, "write"))
    return out


def run():
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.test_utils import check_consistency

    platform = jax.devices()[0].platform
    if platform == "cpu":
        sys.exit("tpu_consistency: no accelerator: "
                 "jax.devices()[0].platform is 'cpu'")

    def check(sym, shapes, grad_req):
        check_consistency(sym, [
            dict(ctx=mx.cpu(), **shapes),
            dict(ctx=mx.tpu(0), **shapes),
        ], grad_req=grad_req)

    ok, failed = 0, []
    for name, sym, shapes, grad_req in cases(mx):
        try:
            check(sym, shapes, grad_req)
            print("PASS %s" % name)
            ok += 1
        except Exception as e:  # noqa: BLE001 - report and continue
            try:
                with jax.default_matmul_precision("highest"):
                    check(sym, shapes, grad_req)
                highest = "PASS"
            except Exception:  # noqa: BLE001 - the diagnostic column
                highest = "FAIL"
            print("FAIL %s [at highest precision: %s]: %s"
                  % (name, highest, str(e)[:200]))
            failed.append(name)
        sys.stdout.flush()
    print("TPU_CONSISTENCY ok=%d fail=%d%s" % (
        ok, len(failed), " failed=" + ",".join(failed) if failed else ""))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(run())
