#!/usr/bin/env python
"""Long-context sequence parallelism (docs/long_context.md): a 4096-token
causal attention sharded over an 8-way ``sp`` mesh with ring attention —
each device holds T/8 of the sequence and K/V blocks rotate around the
ring via collective_permute, so no device ever materializes the full
T x T score matrix. Verified against single-device reference attention.

Runs on 8 virtual CPU devices (the script self-bootstraps XLA_FLAGS
before jax initializes) — the same code path the TPU mesh uses.
"""
import os
import sys

if "--child" not in sys.argv:
    # re-exec with the virtual 8-device CPU platform configured BEFORE
    # jax initializes (appending XLA_FLAGS later has no effect)
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (flags +
                            " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    os.execvpe(sys.executable,
               [sys.executable, os.path.abspath(__file__), "--child"], env)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.ring_attention import (make_ring_attention,
                                               reference_attention)


def main(seed=0, T=4096, H=8, D=32):
    mesh = make_mesh({"sp": 8})
    rng = np.random.RandomState(seed)
    q, k, v = (rng.randn(1, T, H, D).astype(np.float32) * 0.1
               for _ in range(3))

    attn = make_ring_attention(mesh, "sp", causal=True, impl="ring")
    out = np.asarray(attn(q, k, v))

    ref = np.asarray(reference_attention(q, k, v, causal=True))
    err = np.abs(out - ref).max()
    print("T=%d over 8-way sp mesh; max |ring - reference| = %.2e"
          % (T, err))
    assert err < 2e-5, err

    # Ulysses (all-to-all head parallelism) on the same mesh
    attn_u = make_ring_attention(mesh, "sp", causal=True, impl="ulysses")
    err_u = np.abs(np.asarray(attn_u(q, k, v)) - ref).max()
    print("ulysses max err = %.2e" % err_u)
    assert err_u < 2e-5, err_u

    # the point of sequence parallelism: per-device score-block memory
    full = T * T * H * 4 / 2**20
    block = (T // 8) * (T // 8) * H * 4 / 2**20
    print("score memory per device: full %.0f MiB -> ring block %.1f MiB"
          % (full, block))
    print("ring attention OK")


if __name__ == "__main__":
    main()
