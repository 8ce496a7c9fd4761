#!/usr/bin/env python
"""Benchmark the Pallas fast-path kernels against plain XLA on the
current device (VERDICT round-1 item 9: enable MXNET_TPU_PALLAS where it
wins, document parity where it doesn't).

Prints one JSON line per case:
  {"kernel": "fused_linear", "shape": "...", "pallas_us": N,
   "xla_us": N, "speedup": N}

Run on the TPU (the default platform); results are recorded in
docs/pallas.md.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def _time(fn, *args, iters=50):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    tic = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - tic) / iters * 1e6  # us


def bench_fused_linear():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    on_cpu = jax.devices()[0].platform == "cpu"
    cases = [(128, 128, 256, "relu")] if on_cpu else [
        (256, 512, 1024, "relu"),
        (1024, 1024, 1024, "relu"),
        (4096, 2048, 2048, "none"),
        (8192, 4096, 4096, "relu")]
    results = []
    for m, k, n, act in cases:
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(m, k).astype(np.float32))
        w = jnp.asarray(rng.randn(n, k).astype(np.float32))
        b = jnp.asarray(rng.randn(n).astype(np.float32))

        def xla(x, w, b):
            out = x @ w.T + b
            return jnp.maximum(out, 0) if act == "relu" else out

        xla_jit = jax.jit(xla)
        pallas_fn = jax.jit(
            lambda x, w, b: pk.fused_linear(x, w, b, act=act))
        try:
            p = np.asarray(pallas_fn(x, w, b))
            np.testing.assert_allclose(p, np.asarray(xla_jit(x, w, b)),
                                       rtol=2e-2, atol=2e-2)
            pallas_us = _time(pallas_fn, x, w, b)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"kernel": "fused_linear",
                              "shape": "%dx%dx%d" % (m, k, n),
                              "error": str(e)[:200]}))
            continue
        xla_us = _time(xla_jit, x, w, b)
        results.append(("fused_linear", "%dx%dx%d/%s" % (m, k, n, act),
                        pallas_us, xla_us))
    return results


def bench_flash_attention():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    on_cpu = jax.devices()[0].platform == "cpu"
    cases = [(1, 2, 128, 32)] if on_cpu else [
        (4, 8, 512, 64), (2, 8, 2048, 64), (1, 8, 8192, 64)]
    results = []
    for b, h, t, d in cases:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32) * 0.1)
        k = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32) * 0.1)
        v = jnp.asarray(rng.randn(b, t, h, d).astype(np.float32) * 0.1)
        scale = 1.0 / np.sqrt(d)

        def xla(q, k, v):
            s = jnp.einsum("bthd,bshd->bhts", q, k) * scale
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bhts,bshd->bthd", p, v)

        xla_jit = jax.jit(xla)
        pallas_fn = jax.jit(lambda q, k, v: pk.flash_attention(q, k, v))
        try:
            p = np.asarray(pallas_fn(q, k, v))
            np.testing.assert_allclose(p, np.asarray(xla_jit(q, k, v)),
                                       rtol=2e-2, atol=2e-2)
            pallas_us = _time(pallas_fn, q, k, v, iters=20)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"kernel": "flash_attention",
                              "shape": "b%d h%d t%d d%d" % (b, h, t, d),
                              "error": str(e)[:200]}))
            continue
        xla_us = _time(xla_jit, q, k, v, iters=20)
        results.append(("flash_attention", "b%d h%d t%d d%d" % (b, h, t, d),
                        pallas_us, xla_us))
    return results


def main():
    import jax
    dev = jax.devices()[0]
    print(json.dumps({"device": getattr(dev, "device_kind", dev.platform)}))
    for name, shape, pallas_us, xla_us in (bench_fused_linear()
                                           + bench_flash_attention()):
        print(json.dumps({"kernel": name, "shape": shape,
                          "pallas_us": round(pallas_us, 1),
                          "xla_us": round(xla_us, 1),
                          "speedup": round(xla_us / pallas_us, 3)}))


if __name__ == "__main__":
    main()
