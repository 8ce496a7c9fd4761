"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process that owns the TPU drives the main path through the entry
points a user calls, at the full width of ResNet-50 (1000 classes,
3x224x224, bf16 compute, random weights from a seed):

1. ``mx.mod.Module(net, context=mx.tpu(0)).fit(...)`` from an
   ``mx.io.NDArrayIter`` over seeded synthetic data, once on the classic
   forward/backward/update loop and once with ``MXNET_TPU_FUSED_STEP=1``;
2. the trained module re-bound for inference behind
   ``serving.InferenceServer``, single-row requests checked against
   ``mod.predict``;
3. the Pallas kernels a cell's train step takes (and ``rtc``'s entry),
   forward and backward, compiled by Mosaic and compared with the
   ``jax.numpy`` body of the same operator.

Each phase asserts; nothing is retried and nothing falls back to the CPU.
Without a TPU the script exits non-zero before any phase and prints no
result. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

``--devices 4`` instead runs the fused fit over a four-chip mesh
(``kvstore="device_sync"``, then again with ``MXNET_TPU_MESH_FSDP=4``)
and checks what a CPU mesh cannot vouch for: batch shards per device,
param placement, the all-reduce in the compiled step.

The phase functions take the context and sizes as arguments so
``tests/test_chip_smoke.py`` can run them at toy width on ``mx.cpu(0)``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from unittest import mock

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import models, serving, telemetry, xprof
from mxnet_tpu.test_utils import assert_almost_equal

USED_CLASSES = 8   # labels drawn from the first few classes: a signal
#                    eight steps can learn, so "the loss falls" is a test


def synthetic_batch(seed, n, chw, stream=0, used_classes=USED_CLASSES):
    """Seeded class-conditional images: per-class per-channel offset plus
    a coarse spatial pattern (both fixed by ``seed``) under unit noise.
    ``stream`` draws different rows of the same classes — the served
    requests are fresh samples of what the model trained on. Returns
    (data, labels)."""
    c, h, w = chw
    rng = np.random.RandomState(seed)
    coarse = rng.randn(used_classes, c, 4, 4).astype(np.float32)
    proto = np.kron(coarse, np.ones((-(-h // 4), -(-w // 4)),
                                    np.float32))[:, :, :h, :w]
    proto += 2.0 * rng.randn(used_classes, c, 1, 1).astype(np.float32)
    rng = np.random.RandomState([seed, stream])
    labels = rng.randint(0, used_classes, n)
    data = proto[labels] + 0.5 * rng.randn(n, c, h, w).astype(np.float32)
    return data, labels.astype(np.float32)


class _StepLog:
    """batch_end_callback: per-step loss (each epoch is ONE batch, so the
    epoch-reset metric IS that step's loss on the fixed batch) and wall
    seconds per step, fenced by the metric's host fetch."""

    def __init__(self):
        self.losses = []
        self.seconds = []
        self._t = time.perf_counter()

    def __call__(self, param):
        (_, loss), = param.eval_metric.get_name_value()
        now = time.perf_counter()
        self.losses.append(float(loss))
        self.seconds.append(now - self._t)
        self._t = now


def _assert_on_devices(what, arrays, devices):
    want = set(devices)
    for name, arr in arrays:
        got = arr.handle.devices()
        assert got == want, "%s %s lives on %s, expected %s" % (
            what, name, sorted(map(str, got)), sorted(map(str, want)))


def assert_placement(mod, contexts):
    """Every executor arg/grad/aux buffer and every output sits on
    exactly the module's devices — a label that says tpu(0) over a
    buffer on the host is the failure this catches."""
    devices = [c.jax_device() for c in contexts]
    ex = mod._exec_group.executor
    _assert_on_devices("arg", ex.arg_dict.items(), devices)
    _assert_on_devices("grad", ex.grad_dict.items(), devices)
    _assert_on_devices("aux", ex.aux_dict.items(), devices)
    _assert_on_devices("output", zip(ex.output_names, ex.outputs), devices)


def train_phase(net, contexts, chw, batch, steps, fused, kvstore="local",
                seed=0, lr=0.004):
    """``steps`` optimizer steps of ``Module.fit`` on one fixed synthetic
    batch. Asserts placement, a finite falling loss and — fused — exactly
    one dispatch per batch, one compile and no fallback. Returns the
    trained module, the loss stream and the record printed for this path.

    ``lr``: of 0.02, 0.01 and 0.004 tried on the v5e at batch 256 (PR 21),
    0.004 is the one whose loss fell on every step; the others overshoot
    on steps 3-6 before recovering."""
    path = "fused" if fused else "classic"
    data, labels = synthetic_batch(seed, batch, chw)
    train = mx.io.NDArrayIter(data, labels, batch_size=batch)
    mx.random.seed(seed)
    telemetry.reset()
    telemetry.enable()
    log = _StepLog()
    mod = mx.mod.Module(net, context=contexts)
    with mock.patch.dict(os.environ,
                         MXNET_TPU_FUSED_STEP="1" if fused else "0"):
        mod.fit(train, eval_metric="ce", kvstore=kvstore, optimizer="sgd",
                optimizer_params={"learning_rate": lr, "momentum": 0.9},
                initializer=mx.init.Xavier(), num_epoch=steps,
                batch_end_callback=log)
    counters = {k: telemetry.peek(k) or 0
                for k in ("step.dispatches", "step.fused_steps",
                          "step.fused_recompiles", "step.fused_fallback")}
    telemetry.disable()

    assert len(log.losses) == steps, (len(log.losses), steps)
    assert all(np.isfinite(log.losses)), "%s loss not finite: %s" % (
        path, log.losses)
    assert log.losses[-1] < log.losses[0], "%s loss did not fall: %s" % (
        path, log.losses)
    assert_placement(mod, contexts)
    if fused:
        assert mod._fused_step_active, "fused step was requested but " \
            "fit ran the classic loop"
        assert counters["step.fused_fallback"] == 0, counters
        assert counters["step.fused_steps"] == steps, counters
        assert counters["step.dispatches"] == steps, \
            "fused dispatches/step != 1.0: %s" % counters
        assert counters["step.fused_recompiles"] == 1, counters
    else:
        assert not mod._fused_step_active
        assert counters["step.fused_steps"] == 0, counters
    steady = float(np.median(log.seconds[1:])) if steps > 1 else 0.0
    record = {"path": path, "steps": steps, "batch": batch,
              "first_loss": round(log.losses[0], 4),
              "last_loss": round(log.losses[-1], 4),
              # first step = trace + compile (or cache read) + one step
              "first_step_s": round(log.seconds[0], 2),
              "compile_s": round(log.seconds[0] - steady, 2),
              "dispatches_per_step": counters["step.dispatches"] / steps,
              "losses": [round(v, 4) for v in log.losses]}
    print("chip_smoke train %s" % json.dumps(record), flush=True)
    return mod, log.losses, record


def serve_phase(mod, contexts, chw, requests=16, max_batch=16, seed=0):
    """Re-bind the trained module for inference, serve single-row
    requests (one at a time, then as a burst the batcher coalesces) and
    check each served argmax against ``mod.predict``."""
    rows, _ = synthetic_batch(seed, requests, chw, stream=1)
    mod.bind(data_shapes=[("data", (max_batch,) + tuple(chw))],
             label_shapes=[("softmax_label", (max_batch,))],
             for_training=False, force_rebind=True)
    probs = mod.predict(mx.io.NDArrayIter(rows, batch_size=max_batch))
    probs = probs.asnumpy()
    assert probs.shape[0] == requests and np.isfinite(probs).all()
    want = probs.argmax(axis=1)
    top2 = np.sort(probs, axis=1)[:, -2:]
    margin = float((top2[:, 1] - top2[:, 0]).min())
    # the server compiles other batch shapes than predict's, and bf16
    # moves a probability by ~1e-2 between them: "same argmax" is only a
    # claim about the server where the model itself is decisive
    assert margin > 0.1, "the trained model is not decisive (top-2 " \
        "margin %.3g): train longer before comparing argmaxes" % margin
    t0 = time.perf_counter()
    with serving.InferenceServer(mod, top_k=1, max_batch=max_batch,
                                 slo_ms=0.0) as srv:
        first, = srv.infer([rows[:1]], timeout=900.0)
        first_s = time.perf_counter() - t0
        one_by_one = [first] + [srv.infer([rows[i:i + 1]],
                                          timeout=900.0)[0]
                                for i in range(1, requests)]
        burst = [srv.submit([rows[i:i + 1]]) for i in range(requests)]
        burst = [r.get(timeout=900.0)[0] for r in burst]
        stats = srv.stats()
        devices = {c.jax_device() for c in contexts}
        for v in srv._fused._param_vals + srv._fused._aux_vals:
            assert v.devices() == devices, (v.devices(), devices)
    got = np.concatenate(one_by_one).astype(np.int64)
    got_burst = np.concatenate(burst).astype(np.int64)
    assert got.shape == (requests,), got.shape
    assert (got == want).all(), "served argmax %s != predict %s" % (
        got.tolist(), want.tolist())
    assert (got_burst == want).all(), "burst argmax %s != predict %s" % (
        got_burst.tolist(), want.tolist())
    assert stats["compiles"] <= len(srv.buckets), stats
    record = {"requests": 2 * requests, "compiles": stats["compiles"],
              "buckets": list(srv.buckets),
              "first_request_s": round(first_s, 2),
              "min_top2_margin": round(margin, 4)}
    print("chip_smoke serve %s" % json.dumps(record), flush=True)
    return record


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_bwd(fn, args):
    """(out, grads wrt every arg) of ``sum(fn(*args) * cot)`` under one
    jit; the cotangent is a fixed non-uniform ramp so a transposed or
    mis-tiled backward cannot cancel out."""
    import jax
    import jax.numpy as jnp

    def loss(*a):
        out = fn(*a)
        cot = jnp.linspace(0.5, 1.5, out.size,
                           dtype=jnp.float32).reshape(out.shape)
        return (out.astype(jnp.float32) * cot).sum(), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return (out,) + tuple(grads)


#: the kernel phase's cases: ``rtc``'s entry point and the five families of
#: Pallas kernels the language cells run (``ops/pallas_kernels.py``); the
#: ``attention_backward`` cases differentiate through both attention kernels,
#: the ``attention_forward`` ones read the forward kernel's two results
KERNEL_CASES = ("rtc axpy", "ssd_scan", "gated_delta_scan head",
                "gated_delta_scan channel", "gated_delta_rows head",
                "gated_delta_rows channel", "grouped_experts relu2",
                "grouped_experts swiglu", "attention_relayout",
                "attention_backward float32", "attention_backward bfloat16",
                "attention_backward bfloat16 window",
                "attention_forward bfloat16", "attention_forward bfloat16 window",
                "attention_forward bfloat16 narrow",
                "attention_forward bfloat16 wide")


def _kernel_case(name, device, small):
    """``(kernel fn, jax.numpy body, float32 arguments, tolerance)`` of one
    case: both functions are differentiable in every argument, the kernel's
    backward pass its own kernel. Shapes are a language cell's widths over
    1,024 positions, or with ``small`` the least the kernel's rule admits
    over two chunks (a carried state crosses one boundary).

    The tolerances are three times the ``reldiff`` (sum |a - b| / (sum
    |a| + sum |b|), the largest over the result and every gradient) read
    on the v5e at the cells' widths (PR 43, one run of this phase; PERF.md
    section 6): 1.93e-3 for the scan and 2.16e-3 / 2.26e-3 for the
    experts, whose float32 products pass the MXU at the default precision
    inside the kernel where the body's run at ``highest``; 3.9e-7 / 4.5e-7
    for the delta rule, whose kernels ask for ``HIGHEST`` themselves
    (its row-major entry: 4.75e-6, PR 45, the largest over the result and
    seven gradients: ``dt_bias``'s, a float32 sum over every row taken a
    chunk at a time in the kernel); 0 for the relayout pass, which has no
    product; 2.03e-3 / 1.18e-3 for attention's backward pass with float32 /
    bfloat16 operands (PR 46; the float32 case's products pass the MXU at
    the default precision as the experts' do; the bfloat16 case hands both
    sides bfloat16 operands and the body casts them up: what is read is the
    rounding of ``p``, ``ds`` and the results, 1.22e-3 on the interpreter);
    1.05e-3 for each of the forward kernel's four cases (PR 48, the last
    of its chip calls, which read the backward cases as PR 46 did: the
    largest over ``out`` and ``lse``; it is ``out``'s one rounding and
    ``p``'s, the log-sum-exp reads under 1e-6; 1.04e-3 on the
    interpreter)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import attention, moe, seq
    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(5)
    f32 = jnp.dtype("float32")

    def put(*shape, scale=1.0, shift=0.0):
        return jax.device_put(
            (rng.randn(*shape) * scale + shift).astype(np.float32), device)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    if name == "ssd_scan":
        # the Nemotron cell's mixer: 64 heads of 64 in 8 groups, state 128
        dims, chunk, t = ((2, 64, 1, 128), 128, 256) if small \
            else ((64, 64, 8, 128), 128, 1024)
        h, p, g, n = dims
        assert pk.ssd_chunk_applicable(dims, chunk, f32)
        args = (put(1, t, h * p + 2 * g * n),
                jax.nn.softplus(put(1, t, h, shift=-2.0)),    # dt
                -jnp.exp(put(h, scale=0.5)), put(h))          # A, D
        return (lambda *a: seq.ssd_scan(*a, dims, chunk, True),
                lambda *a: seq.ssd_scan(*a, dims, chunk, False), args, 6e-3)
    if name.startswith("gated_delta_scan"):
        channel = name.endswith("channel")
        if channel:
            # the Ling cell's mixer (8 of its 32 heads): 128 keys, 128
            # values, chunks of 64, one decay a key channel
            h, dk, dv, chunk, t = (1, 128, 8, 16, 32) if small \
                else (8, 128, 128, 64, 1024)
            assert pk.delta_channel_applicable((h, dk, dv), chunk, f32)
        else:
            # the Olmo cell's mixer: 15 heads of 96 keys and 192 values
            h, dk, dv, chunk, t = (2, 8, 16, 16, 32) if small \
                else (15, 96, 192, 64, 1024)
            assert pk.delta_chunk_applicable((h, dk, dv), chunk, f32)
        gate = put(1, t, h, dk, shift=-2.0) if channel \
            else put(1, t, h, shift=-2.0)
        args = (unit(put(1, t, h, dk)) * dk ** -0.5, unit(put(1, t, h, dk)),
                put(1, t, h, dv), -jax.nn.softplus(gate),
                jax.nn.sigmoid(put(1, t, h)))
        return (lambda *a: seq.gated_delta_scan(*a, chunk, True),
                lambda *a: seq.gated_delta_scan(*a, chunk, False), args,
                2e-6)
    if name.startswith("gated_delta_rows"):
        # the row-major entry, from the op's rows before their
        # normalisation: the Qwen3-Next cell's mixer (8 of its 32 value
        # heads, two a key head, one decay a head) and the Ling cell's (8
        # of 32, one decay a key channel through the bounded gate formed in
        # the kernel), 128 keys and values, chunks of 64
        channel = name.endswith("channel")
        h, dk, dv, chunk, t = (2, 128, 128, 16, 32) if small \
            else (8, 128, 128, 64, 1024)
        hk, floor = (h, -5.0) if channel else (h // 2, 0.0)
        spec = pk.DeltaRows(t, h, hk, dk, dv, chunk, floor, 1e-6)
        assert pk.delta_rows_applicable((h, dk, dv), hk, chunk, t, channel)
        args = (put(t, hk * dk), put(t, hk * dk), put(t, h * dv))
        if channel:
            args += (put(t, h * dk), jax.nn.sigmoid(put(1, t, h)),
                     jnp.exp(put(1, h * dk, scale=0.5)),
                     put(1, h * dk, shift=-1.0))
        else:
            args += (-jax.nn.softplus(put(1, t, h, shift=-2.0)),
                     jax.nn.sigmoid(put(1, t, h)))

        def body(query, key, value, gate, beta, scale=None, bias=None):
            q, k = (jnp.repeat(unit(x.reshape(1, t, hk, dk)), h // hk, axis=2)
                    for x in (query, key))
            if channel:
                gate = floor * jax.nn.sigmoid(scale * (gate + bias))
                gate = gate.reshape(1, t, h, dk)
            return seq.gated_delta_scan(
                q * dk ** -0.5, k, value.reshape(1, t, h, dv), gate, beta,
                chunk, False).reshape(t, h * dv)

        return (lambda *a: seq.gated_delta_rows(
            *a, *(None,) * (7 - len(a)), spec), body, args, 1.5e-5)
    if name.startswith("grouped_experts"):
        gated = name.endswith("swiglu")
        # 8 held experts of 16 drawn two a row: the Nemotron cell's
        # ``relu2`` experts (2,688 -> 1,856), the GLM cell's gated ones
        # (2,048 -> 1,536)
        rows, hid, f, held = (64, 128, 256, 4) if small \
            else (2048,) + ((2048, 1536) if gated else (2688, 1856)) + (8,)
        top_k, total = 2, 2 * held
        block = 16 if small else moe.block_rows(rows, top_k, total)
        assert pk.grouped_experts_applicable(hid, f, block, f32, gated, rows)
        eid = np.stack([rng.permutation(total)[:top_k]
                        for _ in range(rows)]).astype(np.int32)
        wts = jax.nn.sigmoid(put(rows, top_k))
        *layout, dropped = moe.plan(jax.device_put(eid, device), wts, 0,
                                    held, block)
        assert int(dropped) == 0
        args = (put(rows, hid), wts) + tuple(
            put(held, i, o, scale=i ** -0.5)
            for i, o in [(hid, f)] * (2 if gated else 1) + [(f, hid)])
        loop = moe.grouped_experts_gated if gated else moe.grouped_experts
        # ``wts`` by pair carries the gradient; the layout's copy by slot
        # (``layout[1]``) is a constant, as ``RoutedExperts`` hands it over
        return (lambda x, wts, *ws: moe.grouped_experts_kernel(
            x, ws, wts, *layout, gated),
            lambda x, wts, *ws: loop(x, *ws, wts, *layout), args, 7e-3)
    if name.startswith("attention_forward"):
        # this repo's forward kernel alone, its output AND its log-sum-exp
        # (the backward kernel's residual, which no gradient case reads
        # apart from the output) against the float32 product over the whole
        # ``[T, T]`` scores, bfloat16 operands on both sides: the Ling
        # cell's kind of head (keys of 256 beside values of 128) causal and
        # under a band of 640 keys, LFM2's (a group of four 64-wide heads)
        # and GLM's (256 beside 256), over four blocks of 512; small: two
        # blocks. Not differentiated: ``attention_backward``'s cases are
        # the gradients THROUGH this kernel
        window = 0 if "window" not in name else 384 if small else 640
        hkv, g, d, dv = {"narrow": (2, 4, 64, 64), "wide": (4, 1, 256, 256)
                         }.get(name.split()[-1], (4, 2, 256, 128))
        t = 1024 if small else 2048
        assert pk.attention_applicable(t, d, dv, jnp.bfloat16)
        args = tuple(x.astype(jnp.bfloat16) for x in (
            put(1, hkv, g, t, d, scale=d ** -0.5), put(1, hkv, t, d),
            put(1, hkv, t, dv)))

        def body(q, k, v):
            q, k, v = (x.astype(f32) for x in (q, k, v))
            s = jnp.einsum("bhgtd,bhsd->bhgts", q, k)
            i, j = jnp.arange(t)[:, None], jnp.arange(t)[None]
            s = jnp.where((j <= i) & (j > i - (window or t)), s, -jnp.inf)
            lse = jax.nn.logsumexp(s, axis=-1)
            return jnp.einsum("bhgts,bhsd->bhgtd",
                              jnp.exp(s - lse[..., None]), v), lse

        return (lambda q, k, v: pk.attention_forward(q, k, v, window), body,
                args, 4e-3)
    if name.startswith("attention_backward"):
        # this repo's forward kernel and its one backward kernel
        # against the blockwise body: the Ling cell's kind of head (keys of
        # 256 beside values of 128; 4 groups of two heads here) over four
        # blocks of 512, ten pairs of them; small: a group of two 64-wide
        # heads over two blocks. ``window``: the same under a band of 640
        # keys (a block and a quarter: the edge crosses both pairs behind
        # the diagonal; small: 384, inside the diagonal block too)
        dtype = jnp.dtype(name.split()[1])
        window = 0 if "window" not in name else 384 if small else 640
        b, hkv, g, t, d, dv = (1, 1, 2, 1024, 64, 64) if small \
            else (1, 4, 2, 2048, 256, 128)
        assert pk.attention_applicable(t, d, dv, dtype)
        args = tuple(x.astype(dtype) for x in (
            put(b, hkv, g, t, d, scale=d ** -0.5), put(b, hkv, t, d),
            put(b, hkv, t, dv)))

        def body(q, k, v):
            q, k, v = (x.astype(f32) for x in (q, k, v))
            out = jax.vmap(lambda q, k, v: attention.attend_blockwise(
                q.transpose(2, 0, 1, 3), k.transpose(1, 0, 2),
                v.transpose(1, 0, 2), 1.0, window=window))(q, k, v)
            return out.transpose(0, 2, 3, 1, 4)

        return (lambda q, k, v: attention.attend_splash(q, k, v,
                                                        window=window),
                body, args, 7e-3 if dtype == f32 else 4e-3)
    assert name == "attention_relayout"
    # the GLM cell's queries: 20 heads of 256, the last 64 columns turned;
    # small: two 64-wide heads in one 128-lane tile, turned whole
    batch, t, heads, d, turned = (1, 128, 2, 64, 64) if small \
        else (1, 1024, 20, 256, 64)
    theta, scale = 1e6, d ** -0.5
    tables = tuple(jax.device_put(a, device) for a in
                   attention.relayout_tables(t, theta, turned // 2, d))
    return (lambda x: attention._relaid(
        x, tables, batch=batch, heads=heads, half=turned // 2, scale=scale),
        lambda x: attention.rope(
            x.reshape(batch, t, heads, d), theta, scale, turned,
            pos_axis=1).transpose(0, 2, 1, 3),
        (put(batch * t, heads * d),), 1e-6)


def _rtc_case(device):
    """One runtime-compiled (rtc) kernel through its NDArray entry point."""
    from mxnet_tpu import rtc

    rng = np.random.RandomState(5)
    ctx = mx.Context("cpu" if device.platform == "cpu" else "tpu",
                     device.id)
    x = mx.nd.array(rng.randn(256, 128).astype(np.float32), ctx=ctx)
    y = mx.nd.array(rng.randn(256, 128).astype(np.float32), ctx=ctx)
    out = mx.nd.zeros((256, 128), ctx=ctx)
    axpy = rtc.Rtc("axpy", [("x", x), ("y", y)], [("out", out)],
                   "out_ref[:] = 2.0 * x_ref[:] + y_ref[:]")
    axpy.push([x, y], [out])
    assert out.handle.devices() == {device}
    return assert_almost_equal(
        out.asnumpy(), 2.0 * x.asnumpy() + y.asnumpy(), 1e-6, "rtc axpy")


def kernel_phase(device, small=False, only=KERNEL_CASES):
    """Compile and run each Pallas kernel a cell's train step takes once
    on ``device``, forward and backward, against the ``jax.numpy`` body
    the same operator keeps for shapes the kernel does not admit (float32,
    computed at highest matmul precision). On a TPU the kernels lower
    through Mosaic: the one place a compiled kernel meets its body
    directly (a cell's ``correct`` lets a carried state rounded to bfloat16
    through). On the CPU the same calls lower to the Pallas interpreter."""
    import jax

    results, failures = {}, []
    for name in only:
        try:
            if name == "rtc axpy":
                results[name] = _rtc_case(device)
                continue
            kernel_fn, body_fn, args, tol = _kernel_case(name, device, small)
            # a forward kernel alone gives several results and no gradient
            run = (lambda fn, args: jax.jit(fn)(*args)) \
                if name.startswith("attention_forward") else _fwd_bwd
            with jax.default_matmul_precision("highest"):
                want = run(body_fn, args)
            got = run(kernel_fn, args)
            results[name] = max(
                assert_almost_equal(np.asarray(g, np.float32),
                                    np.asarray(w, np.float32), tol,
                                    "%s[%d]" % (name, i))
                for i, (g, w) in enumerate(zip(got, want)))
        except Exception as e:   # collect every kernel's verdict, then fail
            failures.append("%s: %s: %s" % (
                name, type(e).__name__, str(e).splitlines()[0][:300]))

    print("chip_smoke kernels %s" % json.dumps(
        {"ok": sorted(results), "failed": failures,
         "reldiff": {k: float("%.3g" % v) for k, v in results.items()}}),
        flush=True)
    assert not failures, "Pallas kernels failed:\n  " + "\n  ".join(failures)
    return results


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def mesh_phase(net, contexts, chw, batch, steps, fsdp, ref_losses=None,
               **train_kw):
    """The fused fit over a device mesh under ``kvstore='device_sync'``
    (``fsdp`` > 1 factors it dp x fsdp). Checks per-device batch shards,
    param placement, the collective in the compiled step, and the loss
    stream against the one-device run."""
    n = len(contexts)
    xprof.reset()
    xprof.enable()   # the compile registry holds the step's HLO breakdown
    try:
        with mock.patch.dict(os.environ, MXNET_TPU_MESH_FSDP=str(fsdp)):
            mod, losses, record = train_phase(
                net, contexts, chw, batch, steps, fused=True,
                kvstore="device_sync", **train_kw)
    finally:
        xprof.disable()
    ex = mod._exec_group.executor
    devices = [c.jax_device() for c in contexts]
    shards = ex.arg_dict["data"].handle.addressable_shards
    assert sorted(str(s.device) for s in shards) == \
        sorted(str(d) for d in devices)
    for s in shards:
        assert s.data.shape[0] == batch // n, \
            "device %s holds %d of %d rows" % (s.device, s.data.shape[0],
                                               batch)
    sharded = 0
    for name in mod._param_names:
        arr = ex.arg_dict[name].handle
        assert arr.devices() == set(devices), name
        per_dev = arr.addressable_shards[0].data.shape
        if arr.sharding.is_fully_replicated:
            assert per_dev == arr.shape, name
        else:
            sharded += 1
            assert per_dev[0] * fsdp == arr.shape[0], (name, per_dev)
    assert (sharded > 0) == (fsdp > 1), \
        "%d params sharded under fsdp=%d" % (sharded, fsdp)
    recs = [r for r in xprof.records() if r.site == "fused_step"]
    assert len(recs) == 1, "the step compiled %d times: %s" % (
        len(recs), [r.retrace_cause for r in recs])
    assert not telemetry.peek("compile.aot_fallback"), \
        "the measured AOT executable rejected its arguments"
    assert recs[0].num_devices == n, recs[0].num_devices
    coll = (recs[0].op_breakdown or {}).get("collective", {})
    ops = sorted(coll.get("by_op", {}))
    assert any(op.startswith("all-reduce") for op in ops), \
        "no all-reduce in the compiled step: %s" % ops
    record.update({"mesh": dict(mod._exec_group._mesh.shape),
                   "sharded_params": sharded, "collectives": ops})
    print("chip_smoke mesh %s" % json.dumps(record), flush=True)
    if ref_losses is not None:
        # Same seed, same batch: the first steps are the same function
        # summed in another order, so they agree tightly. After that the
        # tuned lr amplifies bf16 reduction-order noise (v5e, PR 21: dp=4
        # is 4e-4 off one chip at step 1 and 8% off at step 8), so the
        # tail only has to land near the one-device stream.
        for part, rtol in ((slice(0, 2), 1e-2), (slice(2, None), 0.15)):
            np.testing.assert_allclose(
                losses[part], ref_losses[part], rtol=rtol,
                err_msg="loss stream vs one device, steps %s" % (part,))
    return record


# ---------------------------------------------------------------------------

def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--devices", type=int, default=1, choices=(1, 4),
                   help="4: the device_sync / fsdp mesh checks instead")
    args = p.parse_args(argv)

    import jax

    devs = jax.devices()
    print("chip_smoke: platform=%s device_kind=%s devices=%d jax=%s"
          % (devs[0].platform, devs[0].device_kind, len(devs),
             jax.__version__), flush=True)
    if devs[0].platform != "tpu":
        sys.exit("chip_smoke: no TPU: jax.devices()[0].platform is %r"
                 % devs[0].platform)
    if len(devs) < args.devices:
        sys.exit("chip_smoke: --devices %d needs %d TPU devices, found %d"
                 % (args.devices, args.devices, len(devs)))
    xprof.chip_peak_tflops(devs[0].device_kind)   # raises if not in the table

    os.environ["MXNET_COMPUTE_DTYPE"] = "bfloat16"
    net = models.get_resnet50(num_classes=1000)
    chw, batch, steps = (3, 224, 224), 256, 8
    if args.devices == 1:
        ctx = [mx.tpu(0)]
        train_phase(net, ctx, chw, batch, steps, fused=False)
        # the served module trains 4x longer: BatchNorm's moving
        # statistics (momentum 0.9) need ~30 steps to reach the batch's
        # (0.9^32 = 3%), and only then is the inference-mode model the
        # decisive one that was trained
        mod, _, _ = train_phase(net, ctx, chw, batch, 4 * steps, fused=True)
        serve_phase(mod, ctx, chw)
        kernel_phase(devs[0])
    else:
        _, ref, _ = train_phase(net, [mx.tpu(0)], chw, batch, steps,
                                fused=True)
        ctx = [mx.tpu(i) for i in range(args.devices)]
        mesh_phase(net, ctx, chw, batch, steps, fsdp=1, ref_losses=ref)
        mesh_phase(net, ctx, chw, batch, steps, fsdp=args.devices,
                   ref_losses=ref)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
