"""Benchmark: ResNet-50 training throughput (images/sec) on one chip.

Prints ONE JSON line:
  {"metric": "resnet50_train_imgs_per_sec", "value": N, "unit": "img/s",
   "vs_baseline": N, "step_time_ms": N, "tflops_model": N,
   "tflops_xla": N, "mfu_pct": N, "chip": "...", "compute_dtype": "..."}

Baseline: the reference publishes no in-tree ResNet-50 number
(BASELINE.md); the closest per-GPU proxy is ImageNet Inception-BN on
Titan X, batch 128: 1,281,167 img / 10,666 s ~= 120 img/s/GPU
(example/image-classification/README.md:245-253). vs_baseline =
ours / 120.

MFU accounting: tflops_model uses the standard analytic cost (ResNet-50
forward ~= 4.1 GFLOPs/img at 224x224, training ~= 3x forward), the
convention of the "How to Scale Your Model" MFU definition; tflops_xla
uses XLA's own cost analysis of the compiled step (counts every HLO
flop, so it runs higher). mfu_pct = tflops_model / chip bf16 peak.

Set MXNET_TPU_BENCH_TRACE=<dir> to capture a jax profiler trace of the
timed steps (one trace per round for the perf log).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_IMGS_PER_SEC = 120.0  # reference TitanX per-GPU Inception-BN proxy

# analytic training cost per image: 4.089 GFLOPs fwd (He et al. tables,
# 224x224) x3 for fwd+bwd
RESNET50_TRAIN_GFLOPS_PER_IMG = 4.089 * 3


def _run_child(env_overrides, timeout_s):
    """Run the inner bench in a fresh interpreter; return the parsed
    JSON result dict, or None on crash/timeout/unparseable output.

    The child's stdio goes to files, not pipes: a child killed at its
    timeout can leave helper processes holding its fds open, which
    would block a pipe drain."""
    import subprocess
    import tempfile
    env = dict(os.environ)
    env.update(env_overrides)
    here = os.path.dirname(os.path.abspath(__file__))
    with tempfile.NamedTemporaryFile("r", suffix=".bench.out") as out, \
            tempfile.NamedTemporaryFile("r", suffix=".bench.err") as err:
        with open(out.name, "w") as out_w, open(err.name, "w") as err_w:
            child = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)],
                stdout=out_w, stderr=err_w, env=env, cwd=here)
            try:
                rc = child.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
                sys.stderr.write(
                    "bench.py: bench child timed out after %ds\n" % timeout_s)
                return None
        errtxt = err.read()
        if errtxt:
            sys.stderr.write(errtxt[-4000:])
        if rc != 0:
            sys.stderr.write("bench.py: bench child exited rc=%d\n" % rc)
            return None
        for line in out.read().splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except ValueError:
                    pass
    sys.stderr.write("bench.py: bench child printed no JSON line\n")
    return None


def _bench_smoke(procs=4, image=64, num=192, batch=32, seconds=4.0):
    """Input-pipeline-only smoke bench (``--smoke``): single-thread
    decode baseline vs N process workers, entirely host-side — no
    accelerator (or accelerator probe) involved. Prints ONE JSON line
    with ``input_imgs_per_sec`` plus the io.* telemetry of the process
    run so stalls/ring occupancy are inspectable from CI logs."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import tempfile

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from pipeline_bench import make_synthetic_rec, measure
    from mxnet_tpu import telemetry, tracing

    tmp = tempfile.mkdtemp(prefix="bench_smoke_")
    rec = os.path.join(tmp, "synth.rec")
    make_synthetic_rec(rec, num, image)
    base = measure(rec, image, batch, 1, seconds, True, mode="thread")
    telemetry.enable()
    telemetry.reset()
    # MXNET_TPU_METRICS_PORT set -> live /metrics + /healthz during the
    # measured run (the operator-scrape acceptance path)
    server = tracing.maybe_init()
    rate = measure(rec, image, batch, procs, seconds, True, mode="process")
    snap = telemetry.snapshot().get("io", {})
    result = {"metric": "input_imgs_per_sec", "value": round(rate, 1),
              "unit": "img/s", "procs": procs,
              "thread1_baseline": round(base, 1),
              "speedup_vs_thread1": round(rate / base, 2) if base else 0.0,
              "cpu_count": os.cpu_count(), "image": image,
              "platform": "cpu", "io_telemetry": snap}
    if server is not None:
        result["metrics_port"] = server.port
    try:
        result.update(_smoke_xprof_tier())
    except Exception as e:
        sys.stderr.write("bench.py: smoke xprof tier failed: %s\n" % e)
    try:
        result.update(_smoke_serve_tier())
    except Exception as e:
        sys.stderr.write("bench.py: smoke serve tier failed: %s\n" % e)
    telemetry.disable()
    print(json.dumps(result))
    return result


def main():
    """Mode dispatch. The named modes below pin their children to the CPU
    mesh; the default mode runs on the TPU in this process and fails
    without one."""
    # the multichip dp-scaling tier: measured imgs/sec + scaling
    # efficiency on 8 simulated devices; child routing below via env
    # graft: env-ok
    if os.environ.get("MXNET_TPU_BENCH_FSDP"):
        return _bench_fsdp()
    # graft: env-ok
    if os.environ.get("MXNET_TPU_BENCH_MULTICHIP"):
        return _bench_multichip()
    if "multichip" in sys.argv[1:]:
        if "--fsdp" in sys.argv[1:]:
            return _fsdp_main()
        return _multichip_main()
    # the serving tier: continuous-batching inference under open-loop
    # Poisson load on the 8-device mesh ("serve" before the generic
    # --smoke check so `bench.py serve --smoke` routes here)
    # graft: env-ok
    if os.environ.get("MXNET_TPU_BENCH_SERVE_TP"):
        return _bench_serve_tp()
    # graft: env-ok
    if os.environ.get("MXNET_TPU_BENCH_SERVE"):
        return _bench_serve()
    if "serve" in sys.argv[1:]:
        if "--tp" in sys.argv[1:]:
            return _serve_tp_main()
        return _serve_main()
    # the autotune tier: the closed-loop kernel/config search on the
    # forced cpu mesh ("autotune" before the generic --smoke check so
    # `bench.py autotune --smoke` routes here)
    # graft: env-ok
    if os.environ.get("MXNET_TPU_BENCH_AUTOTUNE"):
        return _bench_autotune()
    if "autotune" in sys.argv[1:]:
        return _autotune_main()
    # the fleet tier: fault-tolerant routing over replicas — goodput vs
    # replica count, the killed-replica recovery window, and the rolling
    # param-swap purity proof ("fleet" before the generic --smoke check
    # so `bench.py fleet --smoke` routes here)
    # graft: env-ok
    if os.environ.get("MXNET_TPU_BENCH_FLEET"):
        return _bench_fleet()
    if "fleet" in sys.argv[1:]:
        return _fleet_main()
    # the numerics-observability tier: the fused step timed with the
    # numwatch stats pack off vs armed -> NUMWATCH_health.json
    # graft: env-ok
    if os.environ.get("MXNET_TPU_BENCH_NUMWATCH"):
        return _bench_numwatch()
    if "numwatch" in sys.argv[1:]:
        return _numwatch_main()
    if "--smoke" in sys.argv[1:]:
        import argparse

        p = argparse.ArgumentParser()
        p.add_argument("--smoke", action="store_true")
        p.add_argument("--procs", type=int, default=4)
        p.add_argument("--image", type=int, default=64)
        p.add_argument("--num", type=int, default=192)
        p.add_argument("--batch", type=int, default=32)
        p.add_argument("--seconds", type=float, default=4.0)
        a = p.parse_args()
        return _bench_smoke(a.procs, a.image, a.num, a.batch, a.seconds)
    # default mode: the ResNet-50 step on the chip, in THIS process — no
    # probe child, no orchestrator, no CPU fallback. _bench() exits
    # non-zero before printing anything when jax finds no TPU.
    return _bench()


def _bench_lstm(compute_dtype, steps, key, _force):
    """Words/sec of a PTB-geometry LSTM LM train step: time-major tokens
    -> Embedding -> fused-scan sym.RNN (2x200 lstm) -> vocab softmax,
    fwd+bwd+SGD fused in one jitted computation (reference workload:
    example/rnn/lstm_bucketing.py)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import sym
    from mxnet_tpu.parallel import build_sgd_train_step

    vocab, hidden, layers = 10000, 200, 2
    seq, batch = 35, 32

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    embed = sym.Embedding(data=data, input_dim=vocab, output_dim=hidden,
                          name="embed")
    rnn = sym.RNN(data=embed, state=sym.Variable("rnn_state"),
                  state_cell=sym.Variable("rnn_state_cell"),
                  parameters=sym.Variable("rnn_parameters"),
                  state_size=hidden, num_layers=layers, mode="lstm",
                  name="rnn")
    pred = sym.FullyConnected(sym.Reshape(rnn, shape=(-1, hidden)),
                              num_hidden=vocab, name="pred")
    net = sym.SoftmaxOutput(data=sym.Reshape(pred, shape=(seq, -1, vocab)),
                            label=label, preserve_shape=True,
                            name="softmax")

    rng = np.random.RandomState(0)
    shapes = {"data": (seq, batch)}
    arg_shapes, _, _ = net.infer_shape(**shapes)
    params, feed = {}, {}
    for name, shape in zip(net.list_arguments(), arg_shapes):
        if name == "data":
            feed[name] = jnp.asarray(
                rng.randint(0, vocab, shape), jnp.int32)
        elif name == "softmax_label":
            feed[name] = jnp.asarray(
                rng.randint(0, vocab, shape), jnp.float32)
        elif "state" in name:
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            params[name] = jnp.asarray(rng.randn(*shape) * 0.05,
                                       jnp.float32)
    step, _ = build_sgd_train_step(net, ["data"], ["softmax_label"],
                                   lr=0.1, compute_dtype=compute_dtype)
    jit_step = jax.jit(step, donate_argnums=(0, 2))
    _, params, _ = jit_step(params, feed, [], key)
    _, params, _ = jit_step(params, feed, [],
                            jax.random.fold_in(key, 10_001))
    _force(params)
    tic = time.time()
    for i in range(steps):
        _, params, _ = jit_step(params, feed, [],
                                jax.random.fold_in(key, i))
    _force(params)
    return batch * seq * steps / (time.time() - tic)


def _bench_recordio(jit_step, params, aux, key, batch, image, num_classes,
                    steps, rec_env, _fence, layout="NCHW"):
    """Opt-in end-to-end tier (MXNET_TPU_BENCH_INPUT=1 or =path.rec):
    the same train step fed from ImageRecordIter — recordio decode +
    augment + H2D included — so the pipeline-vs-compute gap is measured,
    not guessed. Returns extra result fields."""
    import tempfile

    import jax
    from mxnet_tpu import io as mio
    from mxnet_tpu import telemetry

    if os.path.isfile(rec_env):
        rec = rec_env
    else:
        here = os.path.dirname(os.path.abspath(__file__))
        sys.path.insert(0, os.path.join(here, "tools"))
        from pipeline_bench import make_synthetic_rec
        tmp = tempfile.mkdtemp(prefix="bench_rec_")
        rec = os.path.join(tmp, "synth.rec")
        make_synthetic_rec(rec, max(2 * batch, 128), image)
    from mxnet_tpu import env as _env

    threads = _env.get("MXNET_TPU_BENCH_THREADS",
                       default=os.cpu_count() or 1) \
        or (os.cpu_count() or 1)
    it = mio.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, image, image), batch_size=batch,
        preprocess_threads=threads, rand_crop=True, rand_mirror=True,
        scale=1.0 / 255.0)

    def batches():
        while True:
            for b in it:
                yield b
            it.reset()

    gen = batches()

    # input-only rate (decode+augment, host side)
    n, tic = 0, time.time()
    while time.time() - tic < 3.0:
        b = next(gen)
        _ = b.data[0].asnumpy().ravel()[0]
        n += batch
    input_rate = n / (time.time() - tic)

    # end-to-end: iterator -> device -> train step (batches arrive NCHW
    # from the iterator; transpose when the winning step is NHWC)
    def _to_layout(arr):
        import jax.numpy as jnp
        return jnp.transpose(arr, (0, 2, 3, 1)) if layout == "NHWC" else arr

    b = next(gen)
    data = {"data": _to_layout(b.data[0]._data.astype(np.float32)),
            "softmax_label": b.label[0]._data.astype(np.float32)}
    _, params, aux = jit_step(params, data, aux, key)
    _fence(params)
    e2e_steps = max(4, steps // 2)
    tic = time.time()
    for i in range(e2e_steps):
        b = next(gen)
        data = {"data": _to_layout(b.data[0]._data.astype(np.float32)),
                "softmax_label": b.label[0]._data.astype(np.float32)}
        _, params, aux = jit_step(params, data, aux,
                                  jax.random.fold_in(key, 1000 + i))
    _fence(params)
    e2e_rate = batch * e2e_steps / (time.time() - tic)
    result = {"input_imgs_per_sec": round(input_rate, 1),
              "e2e_imgs_per_sec": round(e2e_rate, 1),
              "preprocess_threads": threads}

    # cache-fed tier: decode once into a uint8 memmap, crop/mirror/
    # normalize fused on device (io_cache) — the feed path sized to keep
    # the chip busy from ONE host core where per-epoch JPEG decode needs
    # ~28 (docs/performance.md). For a USER-supplied .rec this builds a
    # full decoded copy on disk (ImageNet scale: ~250 GB, hours of
    # decode), so it requires the explicit MXNET_TPU_BENCH_CACHE=1
    # opt-in; the bench's own synthetic rec is always small enough.
    if os.path.isfile(rec_env) \
            and not _env.get("MXNET_TPU_BENCH_CACHE"):
        sys.stderr.write(
            "bench.py: skipping cached e2e tier for user rec %s "
            "(set MXNET_TPU_BENCH_CACHE=1 to decode it into an "
            "on-disk uint8 cache first)\n" % rec)
        return result
    try:
        from mxnet_tpu import io_cache

        prefix = rec + ".cache"
        meta = io_cache.build_decoded_cache(
            rec, prefix, (3, image + 32, image + 32),
            preprocess_threads=threads)
        if meta["num"] < batch:
            # CachedImageRecordIter yields full batches only; fewer
            # records than one batch would make the feed loop spin
            sys.stderr.write(
                "bench.py: cached tier skipped: %d records < batch %d\n"
                % (meta["num"], batch))
            return result
        # device-feed mode: the iterator ships raw uint8 HWC frames
        # (~1/3 the H2D bytes of float32 crops) and crop/mirror/
        # normalize/layout run INSIDE the jitted step below — one XLA
        # dispatch from memmap to updated params
        cit = io_cache.CachedImageRecordIter(
            prefix, (3, image, image), batch, shuffle=True,
            rand_crop=True, rand_mirror=True, scale=1.0 / 255.0,
            device_feed=True, output_layout=layout)

        def cbatches():
            while True:
                try:
                    yield next(cit)
                except StopIteration:
                    cit.reset()

        import jax.numpy as jnp
        nchw = layout != "NHWC"

        def _aug_step(p, a, u8, tops, lefts, mirror, label, k):
            def one(img, t, l):
                return jax.lax.dynamic_slice(img, (t, l, 0),
                                             (image, image, 3))
            crop = jax.vmap(one)(u8, tops, lefts)
            crop = jnp.where(mirror[:, None, None, None],
                             crop[:, :, ::-1], crop)
            x = crop.astype(jnp.float32) * jnp.float32(1.0 / 255.0)
            if nchw:
                x = jnp.transpose(x, (0, 3, 1, 2))
            # nested jit inlines: still exactly one dispatch per batch
            return jit_step(p, {"data": x, "softmax_label": label}, a, k)

        cached_step = jax.jit(_aug_step, donate_argnums=(0, 1))

        def _cstep(b, k):
            aug = b.aug
            return cached_step(
                params, aux, b.data[0]._data,
                np.asarray(aug["tops"], np.int32),
                np.asarray(aug["lefts"], np.int32),
                np.asarray(aug["mirror"], bool),
                b.label[0]._data.astype(np.float32), k)

        cgen = cbatches()
        _, params, aux = _cstep(next(cgen), jax.random.fold_in(key, 2000))
        _fence(params)
        h2d0 = telemetry.peek("ndarray.h2d_bytes") or 0
        tic = time.time()
        for i in range(e2e_steps):
            _, params, aux = _cstep(next(cgen),
                                    jax.random.fold_in(key, 2001 + i))
        _fence(params)
        dt = time.time() - tic
        h2d = (telemetry.peek("ndarray.h2d_bytes") or 0) - h2d0
        result["e2e_cached_imgs_per_sec"] = round(
            batch * e2e_steps / dt, 1)
        # measured uint8 feed bytes vs what float32 crops would move
        f32_bytes = batch * 3 * image * image * 4
        result["e2e_cached_h2d_bytes_per_step"] = h2d // e2e_steps
        result["e2e_cached_h2d_f32_bytes_per_step"] = f32_bytes
        if h2d:
            result["e2e_cached_h2d_ratio"] = round(
                h2d / e2e_steps / float(f32_bytes), 4)
    except Exception as e:
        sys.stderr.write("bench.py: cached e2e tier failed: %s\n" % e)
    return result


def _smoke_xprof_tier(batch=8, nbatches=8):
    """Tiny fused-step train with the xprof registry armed: the smoke
    BENCH record carries ``compile_time_s`` / ``peak_hbm_bytes`` plus the per-site compile summaries (op-category
    breakdown included), so a CPU tier-1 run exercises the whole device
    observability plane end to end."""
    from mxnet_tpu import xprof

    os.environ["MXNET_TPU_FUSED_STEP"] = "1"
    xprof.enable()
    xprof.reset()
    hbm = xprof.HbmWatermark()
    t0 = time.time()
    dps = _bench_fused_dispatch(batch=batch, nbatches=nbatches)
    elapsed = time.time() - t0
    hbm.sample()
    xp = xprof.summary()
    last = (xp["sites"].get("fused_step") or {}).get("last") or {}
    compile_s = xp["totals"]["compile_time_s"]
    xp["bench_analysis"] = xprof.analyze(
        last.get("flops"), last.get("bytes_accessed"),
        step_time_s=max(elapsed - compile_s, 1e-9) / nbatches)
    return {"compile_time_s": round(compile_s, 3),
            "peak_hbm_bytes": int(hbm.peak),
            "dispatches_per_step": dps,
            "xprof": xp}


def _bench_fused_dispatch(batch=8, nbatches=8):
    """XLA dispatches per training batch through Module.fit: ~1.0 when
    the fused train step (MXNET_TPU_FUSED_STEP=1) is active, 3+ on the
    classic forward/backward/update loop. A tiny MLP keeps this a
    dispatch-count probe, not a throughput tier."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    rng = np.random.RandomState(7)
    X = rng.rand(batch * nbatches, 16).astype(np.float32)
    y = rng.randint(0, 4, (batch * nbatches,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    telemetry.enable()
    before = telemetry.peek("step.dispatches") or 0
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9})
    delta = (telemetry.peek("step.dispatches") or 0) - before
    return round(delta / float(nbatches), 2)


def _multichip_tier(dp, per_device_batch=32, dim=128, hidden=256,
                    nbatches=16, epochs=2):
    """One measured dp tier: the sharded fused step (``device_sync``
    kvstore, mean-psum gradient exchange inside the donated jit) driven
    through ``Module.fit`` on ``dp`` simulated devices, weak-scaled
    (global batch = dp x per-device batch). Returns imgs/sec with
    compile time subtracted, the telemetry dispatch count per step, and
    the collective byte fraction from the fused site's HLO op
    breakdown."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry, xprof

    gb = dp * per_device_batch
    rng = np.random.RandomState(11)
    X = rng.rand(gb * nbatches, dim).astype(np.float32)
    y = rng.randint(0, 4, (gb * nbatches,)).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=gb)
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name="fc2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc3")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(dp)])
    telemetry.enable()
    before = telemetry.peek("step.dispatches") or 0
    xprof.enable()
    xprof.reset()
    t0 = time.perf_counter()
    mod.fit(it, num_epoch=epochs, kvstore="device_sync", optimizer="sgd",
            optimizer_params={"learning_rate": 0.05})
    elapsed = time.perf_counter() - t0
    steps = epochs * nbatches
    xp = xprof.summary()
    compile_s = xp["totals"]["compile_time_s"]
    measured = max(elapsed - compile_s, 1e-9)
    dispatches = ((telemetry.peek("step.dispatches") or 0)
                  - before) / float(steps)
    tier = {"dp": dp, "global_batch": gb, "steps": steps,
            "imgs_per_sec": round(steps * gb / measured, 1),
            "step_ms": round(measured / steps * 1e3, 3),
            "compile_time_s": round(compile_s, 3),
            "dispatches_per_step": round(dispatches, 2)}
    bd = (((xp["sites"].get("fused_step") or {}).get("last") or {})
          .get("op_breakdown")) or {}
    c = bd.get("collective")
    if c:
        total_fl = sum(v.get("flops", 0) for v in bd.values())
        total_by = sum(v.get("bytes", 0) for v in bd.values())
        tier["collective"] = {
            "ops": c.get("count", 0),
            "flop_fraction": round(c.get("flops", 0) / total_fl, 4)
            if total_fl else 0.0,
            "byte_fraction": round(c.get("bytes", 0) / total_by, 4)
            if total_by else 0.0}
    return tier


def _bench_multichip():
    """Measured dp-scaling tier (``bench.py multichip``): the sharded
    fused step timed at dp=1,2,4,8 simulated host devices.

    Scaling efficiency is normalized by the host's REAL parallelism:
    ``eff(dp) = rate(dp) / (min(dp, host_cores) * rate(1))``. On actual
    multi-chip hardware every device is its own chip, ``min`` resolves
    to ``dp``, and this is the standard weak-scaling efficiency. On a
    CPU-simulated mesh the forced devices time-slice the host's cores,
    so the ideal aggregate rate is bounded by ``host_cores`` x the
    single-device rate — the ratio then measures what the tier can
    honestly measure there: the throughput retained under GSPMD
    partitioning (sharded feed, in-jit collectives, per-partition
    dispatch), > 1.0 when one sharded dispatch amortizes per-step host
    overhead that dp=1 pays per batch."""
    import jax

    from mxnet_tpu import telemetry

    os.environ["MXNET_TPU_XPROF_OPS"] = "1"
    n_dev = len(jax.devices())
    host_cores = os.cpu_count() or 1
    dps = [d for d in (1, 2, 4, 8) if d <= n_dev]
    # throwaway warmup: the first fit in a process absorbs one-time
    # backend/init cost (~7ms/step on this tier's scale) that would
    # skew whichever dp tier runs first
    _multichip_tier(1, nbatches=4, epochs=1)
    tiers = [_multichip_tier(dp) for dp in dps]
    rate1 = tiers[0]["imgs_per_sec"] or 1e-9
    for t in tiers:
        ideal = min(t["dp"], host_cores) * rate1
        t["scaling_efficiency"] = round(t["imgs_per_sec"] / ideal, 3)
    result = {"metric": "multichip_imgs_per_sec",
              "value": tiers[-1]["imgs_per_sec"], "unit": "img/s",
              "platform": jax.devices()[0].platform,
              "n_devices": n_dev, "host_cores": host_cores,
              "kvstore": "device_sync", "weak_scaling": True,
              "efficiency_normalization":
                  "rate(dp) / (min(dp, host_cores) * rate(1))",
              "tiers": tiers,
              "scaling_efficiency":
                  {str(t["dp"]): t["scaling_efficiency"] for t in tiers},
              "dispatches_per_step":
                  max(t["dispatches_per_step"] for t in tiers),
              "telemetry":
                  {"step": telemetry.snapshot().get("step", {})}}
    coll = tiers[-1].get("collective")
    if coll:
        result["collective"] = coll
    print(json.dumps(result))
    return result


def _multichip_main():
    """Orchestrator for ``bench.py multichip``: run the dp-scaling tier
    in a child interpreter forced onto 8 simulated cpu devices, write
    the record to MULTICHIP_scaling.json, print the one JSON line. Like
    :func:`main` it never imports jax itself."""
    # graft: env-ok
    timeout_s = int(os.environ.get("MXNET_TPU_BENCH_TIMEOUT", 1800))
    # graft: env-ok
    xla = os.environ.get("XLA_FLAGS", "")
    result = _run_child({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS":
            (xla + " --xla_force_host_platform_device_count=8").strip(),
        "MXNET_TPU_BENCH_MULTICHIP": "1",
    }, timeout_s)
    if result is None:
        result = {"metric": "multichip_imgs_per_sec", "value": 0,
                  "incomplete": "multichip bench child failed/timed out"}
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "MULTICHIP_scaling.json")
    # a prior `--fsdp` run's record rides along: the two tiers share
    # the artifact, and a plain dp-scaling rerun must not drop it
    try:
        with open(out) as f:
            prev = json.load(f)
        if isinstance(prev, dict) and "fsdp" in prev:
            result.setdefault("fsdp", prev["fsdp"])
    except (OSError, ValueError):
        pass
    try:
        with open(out, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    print(json.dumps(result))
    return result


def _pack_bytes_per_device(mod):
    """Bytes of params + optimizer state RESIDENT ON DEVICE 0 (summed
    over its shards): the quantity FSDP divides by the fsdp axis size.
    A replicated array contributes its full size (one copy per device);
    an fsdp-sharded one contributes 1/fsdp of it."""
    import jax

    dev0 = jax.devices()[0]

    def on_dev(arr):
        shards = getattr(arr, "addressable_shards", None)
        if shards:
            return sum(int(s.data.nbytes) for s in shards
                       if s.device == dev0)
        return int(getattr(arr, "nbytes", 0))

    ex = mod._exec_group.executor
    total = 0
    for n in mod._param_names:
        if n in ex.arg_dict:
            total += on_dev(ex.arg_dict[n]._data)
    updater = getattr(mod, "_updater", None)
    states = updater.states if updater is not None else {}
    for leaf in jax.tree_util.tree_leaves(states):
        data = getattr(leaf, "_data", None)
        if data is not None:
            total += on_dev(data)
    return total


def _fsdp_tier(fsdp, per_device_batch=32, dim=128, hidden=256,
               nbatches=16, epochs=2):
    """One measured mesh factoring of the SAME model/batch as the
    multichip tier, with momentum SGD so real optimizer state exists to
    shard: ``fsdp<=1`` is the replicated dp-only baseline, ``fsdp>1``
    reshapes the grid into ``(dp, fsdp)`` and the params + momentum
    shard along ``fsdp``. Returns throughput, per-device pack bytes,
    the fused site's per-partition memory_analysis, dispatch count and
    the collective breakdown (with per-opcode sub-buckets)."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry, xprof

    import jax

    n_dev = len(jax.devices())
    # graft: env-ok (child process; the registry re-reads os.environ)
    if fsdp > 1:
        os.environ["MXNET_TPU_MESH_FSDP"] = str(fsdp)
    else:
        os.environ.pop("MXNET_TPU_MESH_FSDP", None)
    try:
        gb = n_dev * per_device_batch
        rng = np.random.RandomState(11)
        X = rng.rand(gb * nbatches, dim).astype(np.float32)
        y = rng.randint(0, 4, (gb * nbatches,)).astype(np.float32)
        it = mx.io.NDArrayIter(X, y, batch_size=gb)
        net = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(net, num_hidden=hidden, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=hidden, name="fc2")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=4, name="fc3")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        mod = mx.mod.Module(net,
                            context=[mx.cpu(i) for i in range(n_dev)])
        telemetry.enable()
        before = telemetry.peek("step.dispatches") or 0
        xprof.enable()
        xprof.reset()
        t0 = time.perf_counter()
        mod.fit(it, num_epoch=epochs, kvstore="device_sync",
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.05,
                                  "momentum": 0.9})
        elapsed = time.perf_counter() - t0
        steps = epochs * nbatches
        xp = xprof.summary()
        compile_s = xp["totals"]["compile_time_s"]
        measured = max(elapsed - compile_s, 1e-9)
        dispatches = ((telemetry.peek("step.dispatches") or 0)
                      - before) / float(steps)
        tier = {"fsdp": fsdp if fsdp > 1 else 1,
                "dp": n_dev // fsdp if fsdp > 1 else n_dev,
                "global_batch": gb, "steps": steps,
                "imgs_per_sec": round(steps * gb / measured, 1),
                "step_ms": round(measured / steps * 1e3, 3),
                "compile_time_s": round(compile_s, 3),
                "dispatches_per_step": round(dispatches, 2),
                "param_opt_bytes_per_device":
                    _pack_bytes_per_device(mod)}
        site = ((xp["sites"].get("fused_step") or {}).get("last")
                or {})
        mem = {k: site.get(k) for k in
               ("argument_bytes", "temp_bytes", "peak_bytes")
               if site.get(k) is not None}
        if mem:
            # memory_analysis is per-partition under SPMD: these are
            # the bytes ONE device holds for the fused executable
            tier["memory_analysis_per_device"] = mem
        bd = site.get("op_breakdown") or {}
        c = bd.get("collective")
        if c:
            total_by = sum(v.get("bytes", 0) for v in bd.values())
            tier["collective"] = {
                "ops": c.get("count", 0),
                "byte_fraction": round(c.get("bytes", 0) / total_by, 4)
                if total_by else 0.0,
                "by_op": {op: dict(v) for op, v in
                          (c.get("by_op") or {}).items()}}
        return tier
    finally:
        os.environ.pop("MXNET_TPU_MESH_FSDP", None)


def _fsdp_parity_probe(fsdp, nbatches=4):
    """Exact-arithmetic witness that the ZeRO exchange is the same
    mean: a linear head on integer data with quarter-integer seed
    weights keeps every product/psum/update a dyadic rational, so the
    dp-only and (dp, fsdp) loss streams and final params must match
    BIT FOR BIT — any rescale or reduce-order bug shows as inequality,
    not as noise."""
    import mxnet_tpu as mx
    from mxnet_tpu import symbol as sym
    from mxnet_tpu.module import Module

    import jax

    n_dev = len(jax.devices())
    batch, dim, hid = n_dev, 4, 8   # 1 row per shard; hid % fsdp == 0

    def run(use_fsdp):
        # graft: env-ok (child process; registry re-reads os.environ)
        if use_fsdp:
            os.environ["MXNET_TPU_MESH_FSDP"] = str(fsdp)
        else:
            os.environ.pop("MXNET_TPU_MESH_FSDP", None)
        try:
            rng = np.random.RandomState(5)
            X = rng.randint(0, 2, (batch * nbatches, dim)) \
                .astype(np.float32)
            # binary labels: with an 8-wide head the mantissa grows
            # ~6 bits/step, so 0..3 labels overflow float32 by step 4
            y = rng.randint(0, 2, (batch * nbatches, hid)) \
                .astype(np.float32)
            net = sym.Variable("data")
            net = sym.FullyConnected(net, num_hidden=hid, name="fc1")
            net = mx.sym.LinearRegressionOutput(net, name="lro")
            arg_shapes, _, _ = net.infer_shape(
                data=(batch, dim), lro_label=(batch, hid))
            prng = np.random.RandomState(9)
            seed = {name: mx.nd.array(
                (prng.randint(-2, 3, shape) * 0.5).astype(np.float32))
                for name, shape in zip(net.list_arguments(),
                                       arg_shapes)
                if name not in ("data", "lro_label")}
            it = mx.io.NDArrayIter(X, y, batch_size=batch,
                                   label_name="lro_label")
            mod = Module(net,
                         context=[mx.cpu(i) for i in range(n_dev)],
                         label_names=("lro_label",))
            stream = []

            def cb(param):
                stream.append(round(dict(
                    param.eval_metric.get_name_value())["mse"], 10))

            mod.fit(it, num_epoch=1, kvstore="device_sync",
                    eval_metric="mse", optimizer="sgd",
                    arg_params=seed, initializer=None,
                    optimizer_params={"learning_rate": 0.5},
                    batch_end_callback=cb)
            args, _ = mod.get_params()
            return stream, {n: a.asnumpy() for n, a in args.items()}
        finally:
            os.environ.pop("MXNET_TPU_MESH_FSDP", None)

    ref_stream, ref_params = run(False)
    sh_stream, sh_params = run(True)
    params_equal = (set(ref_params) == set(sh_params) and all(
        np.array_equal(ref_params[n], sh_params[n])
        for n in ref_params))
    return {"loss_stream_dp": ref_stream,
            "loss_stream_fsdp": sh_stream,
            "loss_stream_equal": ref_stream == sh_stream,
            "params_bit_identical": bool(params_equal)}


def _bench_fsdp():
    """Measured FSDP tier (``bench.py multichip --fsdp``): the same
    8-device mesh factored ``dp=8`` (replicated baseline) vs
    ``dp=2 x fsdp=4`` (params + momentum sharded). The headline metric
    is the per-device params+opt-state byte ratio — ~1/fsdp when every
    array's dim 0 divides — plus the one-dispatch proof, the collective
    op evidence (all-gather/reduce-scatter emitted by GSPMD inside the
    donated jit) and the exact-arithmetic parity witness."""
    import jax

    from mxnet_tpu import telemetry

    os.environ["MXNET_TPU_XPROF_OPS"] = "1"
    n_dev = len(jax.devices())
    fsdp = 4 if n_dev % 4 == 0 else (2 if n_dev % 2 == 0 else 1)
    # throwaway warmup (same reason as the multichip tier)
    _fsdp_tier(1, nbatches=4, epochs=1)
    rep = _fsdp_tier(1)
    sh = _fsdp_tier(fsdp)
    ratio = (sh["param_opt_bytes_per_device"]
             / float(rep["param_opt_bytes_per_device"] or 1))
    parity = _fsdp_parity_probe(fsdp)
    result = {"metric": "fsdp_param_bytes_ratio",
              "value": round(ratio, 4), "unit": "ratio",
              "platform": jax.devices()[0].platform,
              "n_devices": n_dev, "fsdp": fsdp,
              "kvstore": "device_sync",
              "param_bytes_ratio": round(ratio, 4),
              "dispatches_per_step": sh["dispatches_per_step"],
              "replicated": rep, "sharded": sh,
              "parity": parity,
              "telemetry":
                  {"step": telemetry.snapshot().get("step", {})}}
    if sh.get("collective"):
        result["collective"] = sh["collective"]
    print(json.dumps(result))
    return result


def _fsdp_main():
    """Orchestrator for ``bench.py multichip --fsdp``: run the FSDP
    tier in a child forced onto 8 simulated cpu devices and MERGE the
    record under the ``fsdp`` key of MULTICHIP_scaling.json (the plain
    multichip record stays whatever the last plain run wrote). Never
    imports jax itself."""
    # graft: env-ok
    timeout_s = int(os.environ.get("MXNET_TPU_BENCH_TIMEOUT", 1800))
    # graft: env-ok
    xla = os.environ.get("XLA_FLAGS", "")
    result = _run_child({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS":
            (xla + " --xla_force_host_platform_device_count=8").strip(),
        "MXNET_TPU_BENCH_FSDP": "1",
    }, timeout_s)
    if result is None:
        result = {"metric": "fsdp_param_bytes_ratio", "value": 0,
                  "incomplete": "fsdp bench child failed/timed out"}
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "MULTICHIP_scaling.json")
    record = {}
    try:
        with open(out) as f:
            record = json.load(f)
    except (OSError, ValueError):
        record = {}
    record["fsdp"] = result
    try:
        with open(out, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    print(json.dumps(result))
    return result


def _bench_numwatch(batch=8192, dim=256, hidden=256, classes=16,
                    steps=10, warmup=3, reps=10):
    """Measured numerics-observability tier (``bench.py numwatch``):
    the fused train step timed with the numwatch stats pack off vs
    armed on the same MLP, same process. The pack's reductions run
    inside the donated jit, so the armed arm must stay one dispatch per
    step and one trace signature — both are recorded alongside the
    overhead so the gate catches a silent second dispatch, not just a
    slow one.

    Both arms are built up front and their timed windows run as
    adjacent PAIRS with alternating order (base/armed, armed/base, ...);
    the overhead is the MEDIAN of the per-pair deltas over the median
    base window. Sequential phases confound the comparison with host
    drift several times larger than the effect (first-phase allocator
    warmup, cpufreq wander, noisy CI neighbors — observed ±10% between
    back-to-back identical phases on a one-core host, vs the ~1-3%
    being measured): pairing cancels the slow drift, the order flip
    cancels intra-pair bias, the median rejects burst outliers. The
    batch is large so the per-step compute dominates the pack's
    param-sized reductions — the overhead contract is about
    training-scale steps, not toy dispatch latency."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import numwatch, telemetry
    from mxnet_tpu.fused_step import make_fused_step

    os.environ["MXNET_TPU_FUSED_STEP"] = "1"
    telemetry.enable()

    def build(armed):
        if armed:
            os.environ["MXNET_TPU_NUMWATCH"] = "1"
        else:
            os.environ.pop("MXNET_TPU_NUMWATCH", None)
        rng = np.random.RandomState(3)
        X = rng.rand(batch, dim).astype(np.float32)
        y = rng.randint(0, classes, (batch,)).astype(np.float32)
        it = mx.io.NDArrayIter(X, y, batch_size=batch)
        net = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(net, num_hidden=hidden, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mod.init_params()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.01})
        fused = make_fused_step(mod, mx.metric.Accuracy())
        it.reset()
        return fused, mx.metric.Accuracy(), next(iter(it))

    def block(fused):
        ex = fused._executor
        name = ex.arg_names[fused._p_arg_idx[0]]
        jax.block_until_ready(ex.arg_dict[name]._data)

    arms = {"base": build(armed=False), "armed": build(armed=True)}
    os.environ.pop("MXNET_TPU_NUMWATCH", None)
    # warmup compiles each arm exactly once; the armed arm must add
    # exactly ONE fresh trace signature on top of the base arm's
    for fused, metric, b in arms.values():
        r_pre = telemetry.peek("step.fused_recompiles") or 0
        for _ in range(warmup):
            fused.step(b, metric)
        block(fused)
    recompiles = (telemetry.peek("step.fused_recompiles") or 0) - r_pre
    windows = {"base": [], "armed": []}
    armed_steps = 0
    armed_dispatches = 0
    for rep in range(reps):
        order = ("base", "armed") if rep % 2 == 0 else ("armed", "base")
        for name in order:
            fused, metric, b = arms[name]
            d_pre = telemetry.peek("step.dispatches") or 0
            t0 = time.perf_counter()
            for _ in range(steps):
                fused.step(b, metric)
            block(fused)
            windows[name].append((time.perf_counter() - t0) / steps * 1e3)
            if name == "armed":
                armed_steps += steps
                armed_dispatches += \
                    (telemetry.peek("step.dispatches") or 0) - d_pre

    def median(xs):
        xs = sorted(xs)
        mid = len(xs) // 2
        return xs[mid] if len(xs) % 2 else (xs[mid - 1] + xs[mid]) / 2.0

    deltas = [a - b for a, b in zip(windows["armed"], windows["base"])]
    base_ms = median(windows["base"])
    armed_ms = base_ms + median(deltas)
    # the honest error bar: spread of the BASE arm against itself over
    # the run — on a shared one-core host this floor is ~+-5%, which is
    # why the gate's tolerance is sized to it (see bench_baselines.json)
    spread = (max(windows["base"]) - min(windows["base"])) / base_ms * 100
    dps = armed_dispatches / float(armed_steps)
    plane = arms["armed"][0]._numwatch
    plane.fetch()
    overhead = (armed_ms - base_ms) / base_ms * 100.0
    result = {"metric": "numwatch_overhead_pct",
              "value": round(overhead, 2), "unit": "%",
              "platform": jax.devices()[0].platform,
              "overhead_pct": round(overhead, 2),
              "overhead_ok": overhead <= 3.0,
              "baseline_step_ms": round(base_ms, 3),
              "armed_step_ms": round(armed_ms, 3),
              "dispatches_per_step": round(dps, 2),
              "fused_recompiles": int(recompiles),
              "base_window_spread_pct": round(spread, 2),
              "steps_timed": steps, "reps": reps, "batch": batch,
              "tensors": plane.tensor_rows(),
              "guard": {"skipped": int(telemetry.peek(
                            "numwatch.skipped_steps") or 0),
                        "rollbacks": int(telemetry.peek(
                            "numwatch.rollbacks") or 0)},
              "provenance": (None if plane.provenance() is None else
                             dict(zip(("name", "kind", "step"),
                                      plane.provenance()))),
              "health_rows": numwatch.health_rows()[-8:]}
    telemetry.disable()
    print(json.dumps(result))
    return result


def _numwatch_main():
    """Orchestrator for ``bench.py numwatch``: run the numerics
    overhead tier in a child interpreter on the cpu platform, write the
    record to NUMWATCH_health.json, print the one JSON line. Like
    :func:`main` it never imports jax itself."""
    # graft: env-ok
    timeout_s = int(os.environ.get("MXNET_TPU_BENCH_TIMEOUT", 900))
    result = _run_child({
        "JAX_PLATFORMS": "cpu",
        "MXNET_TPU_BENCH_NUMWATCH": "1",
    }, timeout_s)
    if result is None:
        result = {"metric": "numwatch_overhead_pct", "value": 0,
                  "incomplete": "numwatch bench child failed/timed out"}
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "NUMWATCH_health.json")
    try:
        with open(out, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    print(json.dumps(result))
    return result


def _serve_main():
    """Orchestrator for ``bench.py serve [--smoke]``: run the serving
    tier in a child interpreter forced onto 8 simulated cpu devices,
    write the record to SERVE_bench.json, print the one JSON line.
    Like :func:`main` it never imports jax itself."""
    # graft: env-ok
    timeout_s = int(os.environ.get("MXNET_TPU_BENCH_TIMEOUT", 1500))
    # graft: env-ok
    xla = os.environ.get("XLA_FLAGS", "")
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS":
            (xla + " --xla_force_host_platform_device_count=8").strip(),
        "MXNET_TPU_BENCH_SERVE": "1",
    }
    if "--smoke" in sys.argv[1:]:
        env["MXNET_TPU_BENCH_SERVE_SMOKE"] = "1"
    if "--lanes" in sys.argv[1:]:
        env["MXNET_TPU_BENCH_SERVE_LANES"] = "1"
    result = _run_child(env, timeout_s)
    if result is None:
        result = {"metric": "serve_goodput_rps", "value": 0,
                  "unit": "req/s",
                  "incomplete": "serve bench child failed/timed out"}
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "SERVE_bench.json")
    try:
        with open(out, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    print(json.dumps(result))
    return result


def _serve_tp_main():
    """Orchestrator for ``bench.py serve --tp``: run the tensor-
    parallel serving tier in a child forced onto 8 simulated cpu
    devices and MERGE the record under the ``tp`` key of
    SERVE_bench.json (the plain serving record stays whatever the last
    plain run wrote — the tp arm must never clobber the goodput
    baselines). Never imports jax itself."""
    # graft: env-ok
    timeout_s = int(os.environ.get("MXNET_TPU_BENCH_TIMEOUT", 1800))
    # graft: env-ok
    xla = os.environ.get("XLA_FLAGS", "")
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS":
            (xla + " --xla_force_host_platform_device_count=8").strip(),
        "MXNET_TPU_BENCH_SERVE_TP": "1",
    }
    if "--smoke" in sys.argv[1:]:
        env["MXNET_TPU_BENCH_SERVE_SMOKE"] = "1"
    result = _run_child(env, timeout_s)
    if result is None:
        result = {"metric": "serve_tp_goodput_rps", "value": 0,
                  "unit": "req/s",
                  "incomplete": "serve --tp bench child failed/timed out"}
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "SERVE_bench.json")
    record = {}
    try:
        with open(out) as f:
            record = json.load(f)
    except (OSError, ValueError):
        record = {}
    record["tp"] = result
    try:
        with open(out, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    print(json.dumps(result))
    return result


def _autotune_main():
    """Orchestrator for ``bench.py autotune [--smoke]``: run the
    closed-loop kernel/config search (mxnet_tpu/autotune.py) in a child
    interpreter on the forced cpu backend, write the search summary to
    AUTOTUNE_search.json, print the one JSON line. Like :func:`main` it
    never imports jax itself."""
    # graft: env-ok
    timeout_s = int(os.environ.get("MXNET_TPU_BENCH_TIMEOUT", 1200))
    env = {"JAX_PLATFORMS": "cpu", "MXNET_TPU_BENCH_AUTOTUNE": "1"}
    # orchestrator side of the budget knob (never imports mxnet_tpu, so
    # the read stays on os.environ): shrink the search for --smoke
    # unless the operator pinned a budget
    # graft: env-ok
    pinned = os.environ.get("MXNET_TPU_AUTOTUNE_BUDGET_S")
    if "--smoke" in sys.argv[1:] and not pinned:
        env["MXNET_TPU_AUTOTUNE_BUDGET_S"] = "30"
    result = _run_child(env, timeout_s)
    if result is None:
        result = {"metric": "autotune_speedup_vs_default", "value": 0,
                  "unit": "x",
                  "incomplete": "autotune bench child failed/timed out"}
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "AUTOTUNE_search.json")
    try:
        with open(out, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    print(json.dumps(result))
    return result


def _bench_autotune():
    """The measured autotune tier (inner child, forced-cpu mesh): the
    bounded two-site search — the ``norm_act`` row-tile knob and the
    ``conv_backward`` kernel choice — every candidate compiled through
    the registry, pruned or timed, every row fenced through
    mfu_experiments.validate() into MFU_EXPERIMENTS.jsonl, winners
    persisted to the autotune cache. The summary is the proof the loop
    closes: on the cpu interpreter the non-default norm_act row tile
    wins, so ``non_default_winner`` must be true."""
    import jax

    from mxnet_tpu import autotune, xprof

    xprof.enable()
    xprof.reset()
    summary = autotune.run_smoke()
    speedups = [r.get("speedup_vs_default") or 0.0
                for r in summary["sites"].values()]
    result = {"metric": "autotune_speedup_vs_default",
              "value": max(speedups) if speedups else 0.0, "unit": "x",
              "chip": summary["chip"],
              "budget_s": summary["budget_s"],
              "candidates": sum(r["candidates"]
                                for r in summary["sites"].values()),
              "pruned_preflight": sum(r["pruned_preflight"]
                                      for r in summary["sites"].values()),
              "pruned_inapplicable": sum(
                  r["pruned_inapplicable"]
                  for r in summary["sites"].values()),
              "non_default_winner": summary["non_default_winner"],
              "rows_written": summary["rows_written"],
              "rows_refused": summary["rows_refused"],
              "sites": summary["sites"],
              "platform": jax.default_backend()}
    print(json.dumps(result))
    return result


def _serve_tier(srv, rate, duration, slo_ms, rng):
    """One open-loop load tier: Poisson arrivals at ``rate`` req/s for
    ``duration`` seconds, submissions never waiting on completions
    (overload shows up as queue growth -> tail latency, exactly like a
    real load balancer feeding a replica). Returns the tier record,
    including the tier's own occupancy delta, queue-depth percentiles
    and where the adaptive-wait controller ended up."""
    sched = srv.scheduler
    occ0 = sched.occupancy_snapshot()
    sched.drain_depth_samples()
    dim = srv._data_shapes[0][1:]
    row = rng.rand(1, *dim).astype(np.float32)
    reqs = []
    t_next = time.perf_counter()
    t_end = t_next + duration
    while t_next < t_end:
        now = time.perf_counter()
        if t_next > now:
            time.sleep(t_next - now)
        reqs.append(srv.submit([row]))
        t_next += rng.exponential(1.0 / rate)
    lat, failures = [], 0
    for r in reqs:
        try:
            r.get(120)
            lat.append(r.latency_ms)
        except Exception:
            failures += 1
    lat.sort()

    def q(p):
        return round(lat[min(len(lat) - 1, int(p * len(lat)))], 2) \
            if lat else None

    ok = sum(1 for v in lat if v <= slo_ms)
    tier = {"offered_rps": rate, "served": len(lat),
            "failures": failures,
            "achieved_rps": round(len(lat) / duration, 1),
            "goodput_rps": round(ok / duration, 1),
            "p50_ms": q(0.50), "p99_ms": q(0.99), "p999_ms": q(0.999)}
    tier["slo_ok"] = bool(lat) and tier["p99_ms"] <= slo_ms \
        and not failures
    occ1 = sched.occupancy_snapshot()
    db = occ1["batches"] - occ0["batches"]
    tier["mean_occupancy"] = round(
        (occ1["occ_sum"] - occ0["occ_sum"]) / db, 4) if db else 0.0
    depth = sched.drain_depth_samples()
    if depth:
        depth.sort()
        tier["queue_depth"] = {
            "p50": depth[len(depth) // 2],
            "p99": depth[min(len(depth) - 1, int(0.99 * len(depth)))],
            "max": depth[-1]}
    tier["adaptive_wait_ms"] = \
        sched.controller_state()["adaptive_wait_ms"]
    return tier


def _serve_lanes_tier(srv, rate, duration, slo_ms, rng):
    """Mixed-workload tier for ``--lanes``: an interactive Poisson
    stream (70% of the offered rate, deadline = SLO) interleaved with
    a batch-lane stream (30%, 4x looser deadline). Per-lane goodput
    counts a request only against its OWN deadline, so the record
    shows the batch lane riding along without starving and the
    interactive lane holding its deadline."""
    from mxnet_tpu import serving

    dim = srv._data_shapes[0][1:]
    row = rng.rand(1, *dim).astype(np.float32)
    lanes = {"interactive": {"rate": rate * 0.7, "deadline_ms": slo_ms},
             "batch": {"rate": rate * 0.3, "deadline_ms": 4 * slo_ms}}
    reqs = {lane: [] for lane in lanes}
    t0 = time.perf_counter()
    t_end = t0 + duration
    nxt = {lane: t0 + rng.exponential(1.0 / cfg["rate"])
           for lane, cfg in lanes.items()}
    while True:
        lane = min(nxt, key=nxt.get)
        if nxt[lane] >= t_end:
            break
        now = time.perf_counter()
        if nxt[lane] > now:
            time.sleep(nxt[lane] - now)
        cfg = lanes[lane]
        reqs[lane].append(srv.submit([row], priority=lane,
                                     deadline_ms=cfg["deadline_ms"]))
        nxt[lane] += rng.exponential(1.0 / cfg["rate"])
    out = {}
    for lane, cfg in lanes.items():
        lat, shed, failures = [], 0, 0
        for r in reqs[lane]:
            try:
                r.get(120)
                lat.append(r.latency_ms)
            except serving.RequestShed:
                shed += 1
            except Exception:
                failures += 1
        lat.sort()

        def q(p):
            return round(lat[min(len(lat) - 1, int(p * len(lat)))], 2) \
                if lat else None

        good = sum(1 for v in lat if v <= cfg["deadline_ms"])
        out[lane] = {"offered_rps": round(cfg["rate"], 1),
                     "deadline_ms": cfg["deadline_ms"],
                     "served": len(lat), "shed": shed,
                     "failures": failures,
                     "goodput_rps": round(good / duration, 1),
                     "p50_ms": q(0.50), "p99_ms": q(0.99)}
    return out


def _bench_serve():
    """The measured serving tier (inner child, forced-cpu mesh): a
    dp-sharded MLP served through ``serving.InferenceServer``, every
    bucket rung warmed once (all the compiles steady state will ever
    need), then an ascending open-loop Poisson sweep until the p99 SLO
    breaks. The record is the serving counterpart of
    MULTICHIP_scaling.json: requests/sec, goodput at SLO, tail
    latency, occupancy, the per-request latency decomposition, and the
    zero-steady-state-retrace proof off the xprof registry."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import serving, telemetry, tracing, xprof

    telemetry.enable()
    tracing.maybe_init()
    xprof.enable()
    xprof.reset()
    # graft: env-ok
    smoke = bool(os.environ.get("MXNET_TPU_BENCH_SERVE_SMOKE"))
    # graft: env-ok
    lanes_sweep = bool(os.environ.get("MXNET_TPU_BENCH_SERVE_LANES"))

    n_dev = len(jax.devices())
    dp = min(8, n_dev)
    dim, classes, hidden = 64, 16, 128
    max_batch = 32 if smoke else 64
    max_wait_ms = 2.0
    slo_ms = 100.0

    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=[mx.cpu(i) for i in range(dp)])
    mod.bind(data_shapes=[("data", (max_batch, dim))],
             label_shapes=[("softmax_label", (max_batch,))],
             for_training=False)
    mod.init_params(mx.initializer.Uniform(0.07))
    srv = serving.InferenceServer(mod, top_k=1, max_batch=max_batch,
                                  max_wait_ms=max_wait_ms, slo_ms=slo_ms)
    rng = np.random.RandomState(0)
    try:
        # warm every ladder rung ONCE — after this, steady state must
        # never compile again, whatever batch mix the load produces
        for b in srv.buckets:
            srv._fused([np.zeros((b, dim), np.float32)])
        xp0 = (xprof.summary()["sites"].get("fused_infer")
               or {}).get("compiles", 0)
        rc0 = telemetry.peek("infer.recompiles") or 0
        di0 = telemetry.peek("infer.dispatches") or 0
        ba0 = telemetry.peek("serve.batches") or 0

        rates = [50, 150, 300] if smoke else [25, 50, 100, 200, 400, 800]
        duration = 1.5 if smoke else 4.0
        tiers = []
        for rate in rates:
            tier = _serve_tier(srv, rate, duration, slo_ms, rng)
            tiers.append(tier)
            if not tier["slo_ok"]:
                break

        lanes = None
        if lanes_sweep:
            lanes = _serve_lanes_tier(srv, 150 if smoke else 200,
                                      duration, slo_ms, rng)

        xp1 = (xprof.summary()["sites"].get("fused_infer")
               or {}).get("compiles", 0)
        rc1 = telemetry.peek("infer.recompiles") or 0
        di1 = telemetry.peek("infer.dispatches") or 0
        ba1 = telemetry.peek("serve.batches") or 0
        stats = srv.stats()
        traj = srv.scheduler.wait_trajectory()
        lane_counts = srv.scheduler.lane_stats()
        buckets = list(srv.buckets)
        compiles = srv.compiles
    finally:
        srv.close()

    good = [t for t in tiers if t["slo_ok"]]
    best = good[-1] if good else tiers[-1]
    decomp = {}
    for k in ("queue_ms", "sched_idle_ms", "h2d_ms", "dispatch_ms",
              "d2h_ms", "pad_waste_ms", "request_ms"):
        exp = telemetry.histogram("serve." + k).export()
        if exp.get("count"):
            decomp[k] = {"mean": round(exp["mean"], 3),
                         "p50": round(exp["p50"], 3),
                         "p99": round(exp["p99"], 3)}
    if len(traj) > 64:   # downsample evenly; the full ring lives in
        step = len(traj) / 64.0          # the scheduler, not the JSON
        traj = [traj[int(i * step)] for i in range(64)]
    batches = ba1 - ba0
    result = {
        "metric": "serve_goodput_rps",
        "value": best["goodput_rps"], "unit": "req/s",
        "platform": jax.devices()[0].platform,
        "n_devices": n_dev, "dp": dp,
        "buckets": buckets, "max_batch": max_batch,
        "max_wait_ms": max_wait_ms, "slo_ms": slo_ms,
        "adaptive": stats.get("adaptive", False),
        "adaptive_wait_ms": stats.get("adaptive_wait_ms"),
        "requests_per_sec": best["achieved_rps"],
        "goodput_rps_at_slo": best["goodput_rps"],
        "p50_ms": best["p50_ms"], "p99_ms": best["p99_ms"],
        "p999_ms": best["p999_ms"],
        "mean_batch_occupancy": stats.get("mean_occupancy", 0.0),
        "queue_depth": {k: stats[sk] for k, sk in
                        (("p50", "queue_depth_p50"),
                         ("p99", "queue_depth_p99"),
                         ("max", "queue_depth_max"))
                        if stats.get(sk) is not None},
        "compiles": compiles,
        "steady_state_retraces": (rc1 - rc0) + (xp1 - xp0),
        "zero_steady_state_retraces": rc1 == rc0 and xp1 == xp0,
        "dispatches_per_request_batch":
            round((di1 - di0) / batches, 3) if batches else 0.0,
        "latency_decomposition_ms": decomp,
        "adaptive_wait_trajectory": traj,
        "lane_counts": lane_counts,
        "tiers": tiers, "smoke": smoke,
    }
    if lanes is not None:
        result["lanes"] = lanes
    print(json.dumps(result))
    return result


def _bench_serve_tp():
    """The measured tensor-parallel serving tier (``bench.py serve
    --tp``, inner child on the forced-cpu mesh): the same MLP served
    at ``tp=1`` (dp-replicated baseline) and ``tp=2`` (params
    NamedSharding-split along each param's largest divisible dim,
    activations resharded in-graph). The record carries the
    bigger-than-one-chip evidence: per-device resident param bytes
    (~1/tp of the baseline), the preflight proof against a simulated
    chip limit the full pack cannot fit, the xprof collective bucket
    emitted INSIDE the one non-donated dispatch (dispatches/batch
    stays exactly 1.0, zero steady-state retraces), goodput/p99 under
    Poisson load, and the delta-aware weight-streaming experiment
    (second refresh moves only the one perturbed param)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import serving, telemetry, tracing, xprof
    from mxnet_tpu.checkpoint import param_digest

    os.environ["MXNET_TPU_XPROF_OPS"] = "1"
    telemetry.enable()
    tracing.maybe_init()
    xprof.enable()
    xprof.reset()
    # graft: env-ok
    smoke = bool(os.environ.get("MXNET_TPU_BENCH_SERVE_SMOKE"))

    n_dev = len(jax.devices())
    tp = 2 if n_dev % 2 == 0 else 1
    dim, classes, hidden = 64, 16, 128
    max_batch = 32 if smoke else 64
    max_wait_ms = 2.0
    slo_ms = 100.0
    rng = np.random.RandomState(0)

    def build_module():
        net = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(net, num_hidden=hidden, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        mod = mx.mod.Module(net,
                            context=[mx.cpu(i) for i in range(n_dev)])
        mod.bind(data_shapes=[("data", (max_batch, dim))],
                 label_shapes=[("softmax_label", (max_batch,))],
                 for_training=False)
        mod.init_params(mx.initializer.Uniform(0.07))
        return mod

    def dev0_param_bytes(fused):
        """(bytes resident on device 0, total pack bytes) off the
        placed arrays' addressable shards — the same accounting the
        fsdp tier and tests/test_fsdp.py use."""
        dev0 = total = 0
        for v in fused._param_vals:
            total += int(v.nbytes)
            for s in v.addressable_shards:
                if s.device.id == 0:
                    dev0 += int(np.prod(s.data.shape)
                                * s.data.dtype.itemsize)
        return dev0, total

    def run_arm(tp_arm, refresh_probe):
        mod = build_module()
        srv = serving.InferenceServer(mod, top_k=1, max_batch=max_batch,
                                      max_wait_ms=max_wait_ms,
                                      slo_ms=slo_ms, tp=tp_arm)
        try:
            for b in srv.buckets:
                srv._fused([np.zeros((b, dim), np.float32)])
            dev0, total = dev0_param_bytes(srv._fused)
            last = (xprof.summary()["sites"].get("fused_infer")
                    or {}).get("last") or {}
            xp0 = (xprof.summary()["sites"].get("fused_infer")
                   or {}).get("compiles", 0)
            rc0 = telemetry.peek("infer.recompiles") or 0
            di0 = telemetry.peek("infer.dispatches") or 0
            ba0 = telemetry.peek("serve.batches") or 0
            rates = [50, 150] if smoke else [25, 50, 100, 200, 400]
            duration = 1.5 if smoke else 3.0
            tiers = []
            for rate in rates:
                tier = _serve_tier(srv, rate, duration, slo_ms, rng)
                tiers.append(tier)
                if not tier["slo_ok"]:
                    break
            refresh = None
            if refresh_probe:
                refresh = _serve_tp_refresh_probe(srv, mod,
                                                  param_digest)
            xp1 = (xprof.summary()["sites"].get("fused_infer")
                   or {}).get("compiles", 0)
            rc1 = telemetry.peek("infer.recompiles") or 0
            di1 = telemetry.peek("infer.dispatches") or 0
            ba1 = telemetry.peek("serve.batches") or 0
            good = [t for t in tiers if t["slo_ok"]]
            best = good[-1] if good else tiers[-1]
            batches = ba1 - ba0
            bd = last.get("op_breakdown") or {}
            cat_bytes = sum(int(v.get("bytes", 0)) for v in bd.values()
                            if isinstance(v, dict))
            coll = bd.get("collective") or {}
            arm = {"tp": tp_arm,
                   "buckets": list(srv.buckets),
                   "compiles": srv.compiles,
                   "param_bytes_per_device": dev0,
                   "param_bytes_total": total,
                   "goodput_rps": best["goodput_rps"],
                   "p50_ms": best["p50_ms"], "p99_ms": best["p99_ms"],
                   "dispatches_per_request_batch":
                       round((di1 - di0) / batches, 3)
                       if batches else 0.0,
                   "steady_state_retraces": (rc1 - rc0) + (xp1 - xp0),
                   "zero_steady_state_retraces":
                       rc1 == rc0 and xp1 == xp0,
                   "collective": coll,
                   "collective_bytes_fraction":
                       round(coll.get("bytes", 0)
                             / float(cat_bytes), 4) if cat_bytes
                       else 0.0,
                   "tiers": tiers}
            if refresh is not None:
                arm["refresh"] = refresh
            return arm
        finally:
            srv.close()

    base = run_arm(1, refresh_probe=False)
    sharded = run_arm(tp, refresh_probe=True)

    # the bigger-than-one-chip proof: a simulated chip whose HBM holds
    # 75% of the replicated pack — the full pack preflight-refuses,
    # the tp-sharded pack fits with headroom
    limit = int(0.75 * base["param_bytes_per_device"])
    try:
        xprof.preflight_check(base["param_bytes_per_device"], limit,
                              what="replicated param pack")
        oom_msg = None   # pragma: no cover — limit < pack by design
    except Exception as e:   # noqa: BLE001 (MXNetError expected)
        oom_msg = str(e)
    headroom = xprof.preflight_check(
        sharded["param_bytes_per_device"], limit,
        what="tp-sharded param pack")

    ratio = (sharded["param_bytes_per_device"]
             / float(base["param_bytes_per_device"] or 1))
    result = {
        "metric": "serve_tp_goodput_rps",
        "value": sharded["goodput_rps"], "unit": "req/s",
        "platform": jax.devices()[0].platform,
        "n_devices": n_dev, "tp": tp, "dp": n_dev // tp,
        "max_batch": max_batch, "slo_ms": slo_ms,
        "goodput_rps": sharded["goodput_rps"],
        "p50_ms": sharded["p50_ms"], "p99_ms": sharded["p99_ms"],
        "param_bytes_ratio": round(ratio, 4),
        "preflight": {"simulated_limit_bytes": limit,
                      "replicated_refused": oom_msg is not None,
                      "replicated_error": oom_msg,
                      "tp_headroom_bytes": headroom},
        "dispatches_per_request_batch":
            sharded["dispatches_per_request_batch"],
        "zero_steady_state_retraces":
            sharded["zero_steady_state_retraces"],
        "collective": sharded["collective"],
        "collective_bytes_fraction":
            sharded["collective_bytes_fraction"],
        "refresh": sharded.get("refresh"),
        "replicated": base, "sharded": sharded,
        "smoke": smoke,
    }
    print(json.dumps(result))
    return result


def _serve_tp_refresh_probe(srv, mod, param_digest):
    """The delta-aware weight-streaming experiment, run on the live
    (already-warmed) server: refresh once with the full host pack +
    manifest digests (seeds the resident digests — everything moves,
    the ``full_bytes`` denominator), perturb ONE param, refresh again
    — only that param's bytes may cross to the devices. A post-refresh
    dispatch proves the server still serves."""
    args, _ = mod.get_params()
    host = {n: np.asarray(a.asnumpy()) for n, a in args.items()}
    digests = {n: param_digest(v) for n, v in host.items()}
    srv.refresh_params(host_params=host, digests=digests)
    fused = srv._fused
    full_bytes = fused.last_refresh_bytes
    full_ms = fused.last_refresh_ms
    victim = sorted(host)[0]
    host2 = dict(host)
    host2[victim] = host2[victim] + np.float32(0.5)
    digests2 = dict(digests)
    digests2[victim] = param_digest(host2[victim])
    srv.refresh_params(host_params=host2, digests=digests2)
    delta_bytes = fused.last_refresh_bytes
    dim = srv._data_shapes[0][1:]
    srv.submit([np.zeros((1,) + tuple(dim), np.float32)]).get(60)
    return {"full_bytes": full_bytes, "full_ms": round(full_ms, 3),
            "delta_bytes": delta_bytes,
            "delta_ms": round(fused.last_refresh_ms, 3),
            "delta_bytes_ratio":
                round(delta_bytes / float(full_bytes), 4)
                if full_bytes else 0.0,
            "changed_params": fused.last_refresh_changed,
            "skipped_params": fused.last_refresh_skipped,
            "perturbed": victim}


def _smoke_serve_tier(seconds=1.5, rate=80):
    """Mini serving tier for the generic ``--smoke`` record: a tiny
    single-device server under a short Poisson load; the smoke BENCH
    record then carries serving rps/latency next to the io and xprof
    tiers, so CI exercises the batcher end to end."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving

    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=32, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (16, 24))],
             label_shapes=[("softmax_label", (16,))], for_training=False)
    mod.init_params(mx.initializer.Uniform(0.07))
    srv = serving.InferenceServer(mod, top_k=1, max_batch=16,
                                  max_wait_ms=2.0, slo_ms=250.0)
    rng = np.random.RandomState(1)
    try:
        for b in srv.buckets:
            srv._fused([np.zeros((b, 24), np.float32)])
        tier = _serve_tier(srv, rate, seconds, 250.0, rng)
        stats = srv.stats()
    finally:
        srv.close()
    return {"serve": {"requests_per_sec": tier["achieved_rps"],
                      "p50_ms": tier["p50_ms"], "p99_ms": tier["p99_ms"],
                      "mean_batch_occupancy": stats.get("mean_occupancy"),
                      "compiles": stats.get("compiles"),
                      "buckets": stats.get("buckets")}}


def _fleet_main():
    """Orchestrator for ``bench.py fleet [--smoke]``: run the
    fault-tolerant routing tier in a child interpreter on the forced
    cpu backend, write the record to FLEET_bench.json, print the one
    JSON line. Like :func:`main` it never imports jax itself."""
    # graft: env-ok
    timeout_s = int(os.environ.get("MXNET_TPU_BENCH_TIMEOUT", 1500))
    env = {"JAX_PLATFORMS": "cpu", "MXNET_TPU_BENCH_FLEET": "1"}
    if "--smoke" in sys.argv[1:]:
        env["MXNET_TPU_BENCH_FLEET_SMOKE"] = "1"
    result = _run_child(env, timeout_s)
    if result is None:
        result = {"metric": "fleet_goodput_rps", "value": 0,
                  "unit": "req/s",
                  "incomplete": "fleet bench child failed/timed out"}
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "FLEET_bench.json")
    try:
        with open(out, "w") as f:
            json.dump(result, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass
    print(json.dumps(result))
    return result


def _fleet_load(router, rate, duration, rng, row):
    """Open-loop Poisson load on the router: submissions never wait on
    completions; each completion is timestamped, so the caller can bin
    goodput over the wall clock (the killed-replica recovery window
    needs the time axis, not just the totals)."""
    import threading as _threading
    lock = _threading.Lock()
    done = []            # (t_done_s_rel, ok, latency_s)
    t0 = time.perf_counter()
    t_next = t0
    t_end = t0 + duration
    futs = []
    while t_next < t_end:
        now = time.perf_counter()
        if t_next > now:
            time.sleep(t_next - now)
        t_sub = time.perf_counter()

        def _cb(f, t_sub=t_sub):
            t = time.perf_counter()
            with lock:
                done.append((t - t0, f.exception() is None, t - t_sub))

        fut = router.submit([row])
        fut.add_done_callback(_cb)
        futs.append(fut)
        t_next += rng.exponential(1.0 / rate)
    for f in futs:
        try:
            f.result(120)
        except Exception:
            pass
    with lock:
        return list(done), t0


def _fleet_phase_stats(done, duration):
    lat = sorted(l for _, ok, l in done if ok)

    def q(p):
        return round(1e3 * lat[min(len(lat) - 1, int(p * len(lat)))], 2) \
            if lat else None

    return {"served": len(lat),
            "errors": sum(1 for _, ok, _ in done if not ok),
            "achieved_rps": round(len(lat) / duration, 1),
            "p50_ms": q(0.50), "p99_ms": q(0.99)}


def _fleet_double_params(srv):
    """The rolling-swap apply_fn: double every packed param of the
    served executor (stands in for 'the trainer delivered new
    weights'); with the exact-arithmetic demo params the old and new
    outputs are bit-distinguishable."""
    fused = srv._fused
    for i in fused._p_idx:
        arr = fused._ex.arg_arrays[i]
        arr._data = arr._data * 2.0


def _round3(v):
    return None if v is None else round(v, 3)


def _fleet_socket_phase(smoke, rng, row):
    """The socket-transport tier: (a) frame codec vs pickle
    serialization cost per MB; (b) socket-vs-pipe per-request overhead
    at equal open-loop load (the perf claim: p99 within 1.5x of the
    pipe baseline); (c) the chaos acceptance over TCP — net_drop +
    net_partition + net_reorder armed inside the framing layer, zero
    client-visible errors, goodput >= 90% of the clean socket run;
    (d) the disaggregated netfeed epoch — a spawned decode host
    streams batches over loopback into a FeedScheduler and the
    feed-stall p99 proves the chip never starved."""
    import pickle

    from mxnet_tpu import faults, fleet, netfeed, netwire, telemetry

    # (a) serialization: zero-copy frames vs pickle, ms per MB
    payload = [rng.randn(256, 1024).astype(np.float32)]   # 1 MiB
    mb = sum(a.nbytes for a in payload) / (1 << 20)
    reps = 20 if smoke else 50

    def _time(fn):
        fn()                                   # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) * 1e3 / reps

    wire_blob = b"".join(bytes(b) for b in
                         netwire.encode_frame("infer", "m", payload))
    pkl_blob = pickle.dumps(payload, protocol=-1)
    ser = {
        "payload_mb": round(mb, 3),
        # encode builds the sendmsg buffer list — header bytes plus
        # borrowed memoryviews, no payload copy ever happens
        "wire_encode_ms_per_mb": round(_time(
            lambda: netwire.encode_frame("infer", "m", payload)) / mb, 4),
        "wire_decode_ms_per_mb": round(_time(
            lambda: netwire.decode_frame(wire_blob)) / mb, 4),
        "pickle_ms_per_mb": round(_time(
            lambda: pickle.dumps(payload, protocol=-1)) / mb, 4),
        "unpickle_ms_per_mb": round(_time(
            lambda: pickle.loads(pkl_blob)) / mb, 4),
    }

    # (b) + (c): pipe baseline, clean socket, chaos socket — the same
    # open-loop Poisson load through each transport. The rate sits
    # well under either backend's capacity: the claim is per-request
    # overhead at equal load, not a saturation race
    rate = 60 if smoke else 80
    duration = 2.0 if smoke else 5.0

    def _router(backend, **kw):
        kw.setdefault("deadline_ms", 20000.0)
        kw.setdefault("attempt_timeout_ms", 2000.0)
        kw.setdefault("retries", 40)
        kw.setdefault("backoff_ms", 2.0)
        kw.setdefault("health_interval_s", 60.0)
        kw.setdefault("hedge", False)
        return fleet.FleetRouter(backend, 1, **kw)

    def _run(backend, **kw):
        # paired arrival schedule: every transport replays the same
        # Poisson draw, so phase ratios compare completion behaviour
        # rather than arrival-count luck (sigma ~ sqrt(rate*duration))
        prng = np.random.RandomState(20170401)
        with _router(backend, **kw) as r:
            for _ in range(8):                 # warm spawn + compile +
                r.infer([row], timeout=120.0)  # connection dials
            done, _ = _fleet_load(r, rate, duration, prng, row)
            wire = None
            for rid in r.replica_ids():
                rep = r._entries[rid].replica
                if hasattr(rep, "wire_stats"):
                    wire = rep.wire_stats()
            out = _fleet_phase_stats(done, duration)
        if wire:
            out["wire"] = wire
        # load is open-loop and every request eventually completes, so
        # total served just echoes the arrival draw; goodput is what
        # finished INSIDE the measurement window — requests parked in
        # fault-retry past the end are the signal chaos should pay for
        out["in_window"] = sum(1 for t, ok, _ in done
                               if ok and t <= duration)
        return out

    pipe = _run(fleet.in_subprocess("mxnet_tpu.fleet:demo_server_factory"))
    clean = _run(fleet.in_socket("mxnet_tpu.fleet:demo_server_factory"))
    faults.configure("net_drop:0.03,net_partition:0.01,net_reorder:0.08",
                     seed=1)
    try:
        chaos = _run(fleet.in_socket("mxnet_tpu.fleet:demo_server_factory"),
                     attempt_timeout_ms=500.0)
        plan = faults._PLAN
        chaos["injected"] = dict(plan.injected) if plan else {}
    finally:
        faults.configure(None)

    overhead = None
    if pipe["p99_ms"] and clean["p99_ms"]:
        overhead = round(clean["p99_ms"] / pipe["p99_ms"], 3)
    goodput_ratio = None
    if clean["in_window"]:
        goodput_ratio = round(chaos["in_window"] / clean["in_window"], 3)

    # (d) netfeed: a real decode host, one epoch through FeedScheduler
    from mxnet_tpu.io_pipeline import FeedScheduler

    netfeed_rec = {"incomplete": "netfeed epoch did not run"}
    proc, host, port = netfeed.serve_subprocess(
        "mxnet_tpu.netfeed:demo_feed_factory")
    it = netfeed.NetFeedIter(host, port)
    try:
        sched = FeedScheduler(it, depth=2)
        first = sched.next()                   # warm device_put
        telemetry.reset()                      # steady-state stalls only
        telemetry.enable()
        n, nbytes = 1, first.data[0].asnumpy().nbytes
        t0 = time.perf_counter()
        for batch in sched:
            n += 1
            nbytes += batch.data[0].asnumpy().nbytes
            time.sleep(0.002)                  # the "training step"
        wall = time.perf_counter() - t0
        sched.close()
        snap = telemetry.snapshot()
        stall = snap.get("io", {}).get("feed_stall_ms") or {}
        netfeed_rec = {
            "batches": n,
            "payload_mb": round(nbytes / (1 << 20), 2),
            "epoch_s": round(wall, 3),
            "goodput_mb_s": round(nbytes / (1 << 20) / wall, 1)
            if wall else None,
            "feed_stall_p50_ms": _round3(stall.get("p50")),
            "feed_stall_p99_ms": _round3(stall.get("p99")),
            "wait_p99_ms": _round3(
                (snap.get("io", {}).get("netfeed_wait_ms")
                 or {}).get("p99")),
        }
    finally:
        it.close(stop_server=True)
        proc.join(10)
        if proc.is_alive():
            proc.kill()
            proc.join(5)

    return {
        "goodput_rps": clean["achieved_rps"],
        "serialization": ser,
        "pipe": pipe, "clean": clean, "chaos": chaos,
        "overhead_p99_x": overhead,
        "chaos_goodput_ratio": goodput_ratio,
        "netfeed": netfeed_rec,
    }


def _bench_fleet():
    """The measured fleet tier (inner child, forced cpu): a FleetRouter
    over in-process ``demo_server_factory`` replicas.

    Four phases: (1) goodput vs replica count under fixed open-loop
    Poisson load; (2) the chaos acceptance — kill a replica mid-load,
    bin completions into 100ms windows, and measure the recovery time
    until goodput is back to >=90% of the pre-kill rate with ZERO
    client-visible errors; (3) the rolling ``refresh_params`` swap
    under load with the ``torn_swap`` fault armed — every response must
    be pure-old or pure-new bits, none failed; (4) the distributed-
    trace acceptance — subprocess replicas with one armed slow, hedged
    requests traced end to end, the merged clock-aligned tree written
    to FLEET_trace.json."""
    import jax

    from mxnet_tpu import faults, fleet, telemetry

    telemetry.enable()
    # graft: env-ok
    smoke = bool(os.environ.get("MXNET_TPU_BENCH_FLEET_SMOKE"))
    rate = 120 if smoke else 250
    duration = 2.5 if smoke else 6.0
    counts = (1, 2) if smoke else (1, 2, 4)
    rng = np.random.RandomState(0)
    row = (rng.randint(-3, 4, (1, 8))).astype(np.float32)

    def router(n, **kw):
        kw.setdefault("deadline_ms", 20000.0)
        kw.setdefault("attempt_timeout_ms", 2000.0)
        kw.setdefault("retries", 10)
        kw.setdefault("backoff_ms", 2.0)
        kw.setdefault("health_interval_s", 0.02)
        return fleet.FleetRouter(
            fleet.in_process(fleet.demo_server_factory), n, **kw)

    # phase 1: goodput vs replica count
    scaling = []
    for n in counts:
        with router(n) as r:
            (r.infer([row]),)                     # warm the compile
            done, _ = _fleet_load(r, rate, duration, rng, row)
        tier = {"replicas": n, "offered_rps": rate}
        tier.update(_fleet_phase_stats(done, duration))
        scaling.append(tier)

    # phase 2: kill a replica mid-load; recovery window from 100ms bins
    bin_s = 0.1
    r = router(2)
    try:
        r.infer([row])
        kill_after = duration * 0.4
        killer = {}

        def _load_and_kill():
            import threading as _threading

            def _kill():
                time.sleep(kill_after)
                rid = r.replica_ids()[0]
                killer["t"] = time.perf_counter()
                r.kill_replica(rid)

            kt = _threading.Thread(target=_kill, daemon=True)
            kt.start()
            out = _fleet_load(r, rate, duration, rng, row)
            kt.join(10)
            return out

        done, t0 = _load_and_kill()
        chaos_stats = r.stats()
    finally:
        r.close()
    t_kill = killer["t"] - t0
    n_bins = int(duration / bin_s) + 1
    bins = [0] * n_bins
    for t, ok, _ in done:
        if ok and t < duration:
            bins[int(t / bin_s)] += 1
    pre_bins = [b for i, b in enumerate(bins)
                if 0.5 <= i * bin_s and (i + 1) * bin_s <= t_kill]
    pre_rps = (sum(pre_bins) / (len(pre_bins) * bin_s)) if pre_bins \
        else 0.0
    post = [(i, b) for i, b in enumerate(bins) if i * bin_s >= t_kill]
    recovery_ms = None
    for i, b in post:
        if b / bin_s >= 0.9 * pre_rps:
            recovery_ms = round(((i + 1) * bin_s - t_kill) * 1e3, 1)
            break
    window = [b / bin_s for i, b in post[:int(1.0 / bin_s)]]
    chaos = {"offered_rps": rate,
             "pre_kill_goodput_rps": round(pre_rps, 1),
             "kill_window_min_goodput_rps":
                 round(min(window), 1) if window else None,
             "recovery_ms": recovery_ms,
             "recovered_to_90pct": recovery_ms is not None,
             "client_errors": sum(1 for _, ok, _ in done if not ok),
             "replica_crashes":
                 chaos_stats["counters"].get("replica_crashes", 0),
             "respawns": chaos_stats["counters"].get("respawns", 0),
             "retries": chaos_stats["counters"].get("retries", 0),
             "recovered_requests":
                 chaos_stats["counters"].get("recovered_requests", 0)}

    # phase 3: rolling swap under load, torn_swap fault ARMED — the
    # drain must mask the torn window: pure-old or pure-new, never mixed
    faults.configure("torn_swap", slow_ms=20.0)
    try:
        r = router(2, health_interval_s=60.0)
        try:
            (old,) = r.infer([row])
            ref = fleet.InProcReplica("ref", fleet.demo_server_factory)
            try:
                _fleet_double_params(ref._srv)
                ref._srv.refresh_params()
                (new,) = ref.submit([row]).wait(30)
            finally:
                ref.close()
            stop = {"v": False}
            outs, failed = [], [0]

            def _swap_load():
                while not stop["v"]:
                    try:
                        (o,) = r.infer([row])
                        outs.append(o)
                    except Exception:
                        failed[0] += 1

            import threading as _threading
            threads = [_threading.Thread(target=_swap_load, daemon=True)
                       for _ in range(3)]
            for t in threads:
                t.start()
            time.sleep(0.3)
            r.refresh_params(apply_fn=_fleet_double_params,
                             drain_timeout_s=30.0)
            time.sleep(0.3)
            stop["v"] = True
            for t in threads:
                t.join(30)
            n_old = sum(bool(np.array_equal(o, old)) for o in outs)
            n_new = sum(bool(np.array_equal(o, new)) for o in outs)
            swap_stats = r.stats()
        finally:
            r.close()
        plan = faults.active() and faults._PLAN
        swap = {"responses": len(outs), "failed": failed[0],
                "mixed_version": len(outs) - n_old - n_new,
                "old_version": n_old, "new_version": n_new,
                "swaps": swap_stats["counters"].get("param_swaps", 0),
                "torn_injected":
                    plan.injected.get("torn_swap", 0) if plan else 0}
    finally:
        faults.configure(None)

    # phase 4: distributed trace of a hedged request — SUBPROCESS
    # replicas this time (real OS processes beside the router). The
    # first replica spawns with ``slow_replica`` armed through the
    # inherited env, so first attempts stall past the primed p95 and
    # hedge to the cleanly-spawned second replica; the tail sampler
    # must keep the hedged trees, and each must hold the winning AND
    # the abandoned attempt with replica-side spans from two child
    # pids, clock-aligned onto the router's wall clock.
    from mxnet_tpu import dtrace

    os.environ["MXNET_TPU_FAULTS"] = "slow_replica"
    os.environ["MXNET_TPU_FAULT_SLOW_MS"] = "60"
    try:
        r = fleet.FleetRouter(
            fleet.in_subprocess("mxnet_tpu.fleet:demo_server_factory"),
            1, deadline_ms=30000.0, attempt_timeout_ms=5000.0,
            retries=10, backoff_ms=2.0, hedge=True,
            health_interval_s=60.0)
    finally:
        del os.environ["MXNET_TPU_FAULTS"]
        del os.environ["MXNET_TPU_FAULT_SLOW_MS"]
    trace = {"hedged_trace": None, "pids": 0, "nested": False}
    try:
        r.add_replica()          # clean env: the fast hedge target
        # warm both children's one-time compile UNTRACED (session ids
        # walk the hash ring, so a handful covers both replicas)
        for i in range(16):
            r.infer([row], session="warm%d" % i)
        dtrace.enable()
        for _ in range(12):
            with r._rlock:       # pin the hedge trigger at ~p95=4ms
                r._lat.clear()
                r._lat.extend([0.004] * 30)
            r.infer([row])
        time.sleep(0.5)          # let hedge losers' late replies land
        trace.update(dtrace.stats())
        for ent in dtrace.kept_traces():
            if ent["kept"] != "hedge":
                continue
            spans = ent["spans"]
            atts = [s for s in spans if s["name"] == "fleet.attempt"]
            won = [a for a in atts if a["tags"].get("won")]
            lost = [a for a in atts if a["tags"].get("abandoned")]
            if not (won and lost):
                continue

            def _child_pids(att):
                return {s["pid"] for s in spans
                        if s["parent"] == att["span"]
                        and s["pid"] != att["pid"]}

            pids_w, pids_l = _child_pids(won[0]), _child_pids(lost[0])
            if not (pids_w and pids_l):
                continue
            root = next(s for s in spans if s["parent"] == "")
            lo, hi = root["ts"], root["ts"] + root["dur"]
            eps = 0.025
            nested = all(lo - eps <= s["ts"]
                         and s["ts"] + s["dur"] <= hi + eps
                         for s in spans
                         if s["parent"] == won[0]["span"])
            trace.update({
                "hedged_trace": ent["trace_id"],
                "pids": len({root["pid"]} | pids_w | pids_l),
                "nested": nested,
                "spans_in_tree": len(spans)})
            if nested:
                break
        trace_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "FLEET_trace.json")
        trace["events"] = dtrace.write_chrome_trace(trace_path)
    finally:
        r.close()
        dtrace.disable()

    # phase 5: the watchtower — rerun the steady load under obswatch
    # federation and prove the fleet rollup agrees with the client's
    # own measurements, then seed an SLO burn (slow_replica fault) and
    # prove the multi-window burn-rate alert fires before the error
    # budget is spent. Rollups land in the durable .obswatch store and
    # the whole time-series artifact goes to OBS_fleet.json.
    import shutil

    from mxnet_tpu import obswatch

    here = os.path.dirname(os.path.abspath(__file__))
    obs_dir = os.path.join(here, ".obswatch")
    shutil.rmtree(obs_dir, ignore_errors=True)   # one run, one series
    store = obswatch.TimeSeriesStore(obs_dir, seg_records=2048,
                                     seg_keep=4)

    # (a) federation agreement: manual ticks bracket the load so the
    # counter deltas cover exactly the measured window
    with router(2) as r:
        # warm both compiles, timing each call: the router histogram is
        # cumulative, so the client reference must cover the same
        # request population (warmup + load) for a fair p99 comparison
        warm_lats = []
        for i in range(16):
            t_w = time.perf_counter()
            r.infer([row], session="ow%d" % i)
            warm_lats.append(time.perf_counter() - t_w)
        watch = obswatch.ObsWatch(
            r, store=store,
            monitor=obswatch.BurnRateMonitor(
                slo_target=0.99, fast_s=5.0, slow_s=60.0,
                threshold=14.4),
            interval_ms=3600e3)                  # manual ticks only
        try:
            r0 = watch.tick()
            done, _ = _fleet_load(r, rate, duration, rng, row)
            r1 = watch.tick()
        finally:
            watch.close()
    obs_client = _fleet_phase_stats(done, duration)
    fed_goodput = obswatch.goodput(r0, r1)
    fed_fleet = r1.get("fleet") or {}
    fed_p99 = fed_fleet.get("p99_ms")
    ref = sorted(warm_lats + [l for _, ok, l in done if ok])
    client_p99 = round(
        1e3 * ref[min(len(ref) - 1, int(0.99 * len(ref)))], 3) \
        if ref else None

    def _rel_err(measured, reference):
        if measured is None or not reference:
            return None
        return abs(measured - reference) / reference

    goodput_err = _rel_err(fed_goodput, obs_client["achieved_rps"])
    p99_err = _rel_err(fed_p99, client_p99)
    obs = {"fed_goodput_rps": (None if fed_goodput is None
                               else round(fed_goodput, 1)),
           "client_goodput_rps": obs_client["achieved_rps"],
           "goodput_rel_err": (None if goodput_err is None
                               else round(goodput_err, 4)),
           "fed_p50_ms": fed_fleet.get("p50_ms"),
           "fed_p99_ms": fed_p99,
           "fed_p999_ms": fed_fleet.get("p999_ms"),
           "client_p99_ms": client_p99,
           "client_load_p99_ms": obs_client["p99_ms"],
           "p99_rel_err": (None if p99_err is None
                           else round(p99_err, 4)),
           "replicas_up": fed_fleet.get("up"),
           "store_dir": os.path.relpath(obs_dir, here)}

    # (b) seeded SLO burn: one-in-two batches stalls past the SLO, so
    # the fleet burns budget at ~2x sustainable (slo_target=0.75 budget
    # with ~50% bad) — the fast+slow windows must both trip the alert
    # while budget_spent < 1
    faults.configure("slow_replica:0.5", slow_ms=15.0)
    burn = {"alert_fired": False, "alert_at_s": None,
            "budget_spent_at_alert": None, "fast_burn": None,
            "slow_burn": None}
    try:
        def _slo_factory():
            srv = fleet.demo_server_factory()
            srv.scheduler.slo_ms = 10.0          # breached by the fault
            return srv

        burn_rate = 60 if smoke else 120
        fast_s, slow_s = (0.8, 3.2) if smoke else (1.0, 6.0)
        r = fleet.FleetRouter(
            fleet.in_process(_slo_factory), 2, deadline_ms=20000.0,
            attempt_timeout_ms=2000.0, retries=10, backoff_ms=2.0,
            health_interval_s=60.0)
        try:
            for i in range(16):
                r.infer([row], session="bw%d" % i)
            watch = obswatch.ObsWatch(
                r, store=store,
                monitor=obswatch.BurnRateMonitor(
                    slo_target=0.75, fast_s=fast_s, slow_s=slow_s,
                    threshold=1.5, min_events=20),
                interval_ms=100.0)
            try:
                t_burn0 = watch.tick()["ts"]
                watch.start()
                _fleet_load(r, burn_rate, duration, rng, row)
            finally:
                watch.close()
        finally:
            r.close()
        for rec in store.records():
            v = rec.get("burn") or {}
            if v.get("alert") and rec.get("ts", 0.0) >= t_burn0:
                burn.update({
                    "alert_fired": True,
                    "alert_at_s": round(rec["ts"] - t_burn0, 3),
                    "budget_spent_at_alert": v.get("budget_spent"),
                    "fast_burn": v.get("fast_burn"),
                    "slow_burn": v.get("slow_burn")})
                break
    finally:
        faults.configure(None)

    obs_ok = bool(goodput_err is not None and goodput_err <= 0.05
                  and p99_err is not None and p99_err <= 0.05)
    burn_ok = bool(burn["alert_fired"]
                   and burn["budget_spent_at_alert"] is not None
                   and burn["budget_spent_at_alert"] < 1.0)
    obs_art = {
        "metric": "obswatch_fleet_goodput_rps",
        "value": obs["fed_goodput_rps"] or 0, "unit": "req/s",
        "federation": obs, "final_rollup": r1, "burn": burn,
        "series": {name: store.query(name) for name in
                   ("fleet.p99_ms", "fleet.served",
                    "fleet.slo_breaches", "burn.fast_burn",
                    "burn.slow_burn", "burn.budget_spent")},
        "obs_ok": obs_ok, "burn_ok": burn_ok, "smoke": smoke,
    }
    try:
        with open(os.path.join(here, "OBS_fleet.json"), "w") as f:
            json.dump(obs_art, f, indent=2, sort_keys=True)
            f.write("\n")
    except OSError:
        pass

    # phase 6: the socket transport — serialization vs pickle, the
    # socket-vs-pipe overhead claim, chaos over TCP, and the netfeed
    # epoch (the zero-copy wire's whole acceptance record)
    try:
        sock = _fleet_socket_phase(smoke, rng, row)
    except Exception as e:   # noqa: BLE001 (recorded, never fatal)
        sock = {"incomplete": "socket phase failed: %s" % e}
    sock_ok = bool(
        "incomplete" not in sock
        and sock["chaos"]["errors"] == 0
        and (sock["chaos_goodput_ratio"] or 0) >= 0.9
        and (sock["overhead_p99_x"] or 99) <= 1.5)

    best = max(scaling, key=lambda t: t["achieved_rps"])
    result = {
        "metric": "fleet_goodput_rps",
        "value": best["achieved_rps"], "unit": "req/s",
        "platform": jax.devices()[0].platform,
        "replicas_best": best["replicas"],
        "scaling": scaling, "chaos": chaos, "swap": swap,
        "chaos_ok": (chaos["client_errors"] == 0
                     and chaos["recovered_to_90pct"]),
        "swap_ok": (swap["failed"] == 0 and swap["mixed_version"] == 0
                    and swap["torn_injected"] >= 2),
        "trace": trace,
        "trace_ok": (trace["hedged_trace"] is not None
                     and trace["pids"] >= 3 and trace["nested"]),
        "obs": obs, "burn": burn,
        "obs_ok": obs_ok, "burn_ok": burn_ok,
        "socket": sock, "socket_ok": sock_ok,
        "smoke": smoke,
    }
    print(json.dumps(result))
    return result


def _bench():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("bench.py: no TPU: jax.devices()[0].platform is %r; the "
                 "default mode measures the chip and has no CPU fallback"
                 % devices[0].platform)

    import mxnet_tpu as mx
    from mxnet_tpu import models, telemetry, tracing, xprof
    from mxnet_tpu.parallel import build_sgd_train_step

    telemetry.enable()
    tracing.maybe_init()
    # arm the device observability plane: every step-path compile below
    # lands in the registry, and the BENCH record carries the summary
    xprof.enable()
    xprof.reset()

    from mxnet_tpu import env as _env

    batch = _env.get("MXNET_TPU_BENCH_BATCH", default=256) or 256
    image = 224
    num_classes = 1000
    steps = _env.get("MXNET_TPU_BENCH_STEPS", default=20) or 20

    net = models.get_resnet50(num_classes=num_classes)
    rng = np.random.RandomState(0)

    def _random_feeds(a_net, data_shape, n_class):
        """Random params/data/aux for a softmax net, placed on the
        bench device — one init rule for every measured tier."""
        a_shapes, _, x_shapes = a_net.infer_shape(data=data_shape)
        p, d = {}, {}
        for name, shape in zip(a_net.list_arguments(), a_shapes):
            if name == "data":
                d[name] = jax.device_put(
                    rng.rand(*shape).astype(np.float32), devices[0])
            elif name == "softmax_label":
                d[name] = jax.device_put(
                    rng.randint(0, n_class, shape).astype(np.float32),
                    devices[0])
            elif name.endswith("gamma"):
                p[name] = jax.device_put(
                    np.ones(shape, dtype=np.float32), devices[0])
            else:
                p[name] = jax.device_put(
                    (rng.randn(*shape) * 0.05).astype(np.float32),
                    devices[0])
        x = [jax.device_put(np.ones(s, dtype=np.float32) if "var" in n
                            else np.zeros(s, dtype=np.float32), devices[0])
             for n, s in zip(a_net.list_auxiliary_states(), x_shapes)]
        return p, d, x

    params, data, aux = _random_feeds(net, (batch, 3, image, image),
                                      num_classes)

    # bf16 activations/matmuls with f32 master weights — the idiomatic
    # TPU precision (MXU native); override with MXNET_TPU_BENCH_DTYPE
    import jax.numpy as jnp
    dtype_name = _env.get("MXNET_TPU_BENCH_DTYPE") or "bfloat16"
    compute_dtype = None if dtype_name == "float32" \
        else getattr(jnp, dtype_name)
    step, _ = build_sgd_train_step(net, ["data"], ["softmax_label"],
                                   lr=0.01, compute_dtype=compute_dtype)
    # donate params/aux so XLA reuses their HBM buffers across steps
    jit_step = jax.jit(step, donate_argnums=(0, 2))
    key = jax.random.PRNGKey(0)

    # XLA's own flop count of the compiled whole-graph train step, with
    # the compile wall time, memory analysis and op-category breakdown
    # recorded through the xprof compile registry
    tic_c = time.time()
    compiled = jit_step.lower(params, data, aux, key).compile()
    compile_time_s = time.time() - tic_c
    bench_rec = xprof.record_compile("bench.train_step", compiled,
                                     compile_time_s)
    xla_flops = bench_rec.flops or 0.0

    def _force(tree):
        # fetch a scalar: block_until_ready alone can under-synchronize
        # through remote-device transports, inflating throughput
        leaf = next(iter(tree.values())) if isinstance(tree, dict) else tree
        return float(np.asarray(leaf.sum()))

    # warmup / compile (two steps: the donated-buffer steady state)
    outputs, params, aux = jit_step(params, data, aux, key)
    outputs, params, aux = jit_step(params, data, aux,
                                    jax.random.fold_in(key, steps + 1))
    _force(params)
    # live-buffer watermark, sampled outside the timed window so the
    # accounting never perturbs the throughput number
    hbm_wm = xprof.HbmWatermark()
    hbm_wm.sample()

    trace_dir = _env.get("MXNET_TPU_BENCH_TRACE")
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    tic = time.time()
    t_last = time.perf_counter()
    for i in range(steps):
        with telemetry.span("bench.step"):
            outputs, params, aux = jit_step(params, data, aux,
                                            jax.random.fold_in(key, i))
        now = time.perf_counter()
        tracing.record_step((now - t_last) * 1e3)
        t_last = now
    _force(params)
    elapsed = time.time() - tic
    if trace_dir:
        jax.profiler.stop_trace()

    imgs_per_sec = batch * steps / elapsed
    layout = "NCHW"
    # round-3 measured experiment: time the SAME step with the
    # channels-last tower (weights are OIHW in both layouts so params carry over)
    # and let the faster layout own the headline number.
    net2 = models.get_resnet50(num_classes=num_classes, layout="NHWC")
    step2, _ = build_sgd_train_step(
        net2, ["data"], ["softmax_label"], lr=0.01,
        compute_dtype=compute_dtype)
    jit2 = jax.jit(step2, donate_argnums=(0, 2))
    data2 = dict(data)
    data2["data"] = jnp.transpose(data["data"], (0, 2, 3, 1))
    # donate COPIES: the first jit2 call must not consume the
    # baseline's params/aux buffers — the losing-NHWC path (and
    # the recordio tier) keeps using them
    p2 = {k: jnp.copy(v) for k, v in params.items()}
    a2 = [jnp.copy(v) for v in aux]
    _, p2, a2 = jit2(p2, data2, a2, key)
    _, p2, a2 = jit2(p2, data2, a2,
                     jax.random.fold_in(key, steps + 2))
    _force(p2)
    tic2 = time.time()
    for i in range(steps):
        _, p2, a2 = jit2(p2, data2, a2,
                         jax.random.fold_in(key, i))
    _force(p2)
    nhwc_rate = batch * steps / (time.time() - tic2)
    if nhwc_rate > imgs_per_sec:
        layout = "NHWC"
        imgs_per_sec = nhwc_rate
        elapsed = batch * steps / nhwc_rate
        params, aux, data = p2, a2, data2
        jit_step = jit2

    # CIFAR-10 Inception-BN-28-small: the reference's PUBLISHED
    # headline (842 img/s on one GTX 980, batch 128 —
    # example/image-classification/README.md:202-206), measured with
    # the same protocol so vs_baseline_cifar is apples-to-apples
    # against the reference's own number.
    cnet = models.get_inception_bn_28_small(num_classes=10)
    cbatch = 128
    cparams, cdata, caux = _random_feeds(cnet,
                                         (cbatch, 3, 28, 28), 10)
    cstep, _ = build_sgd_train_step(
        cnet, ["data"], ["softmax_label"], lr=0.01,
        compute_dtype=compute_dtype)
    cjit = jax.jit(cstep, donate_argnums=(0, 2))
    _, cparams, caux = cjit(cparams, cdata, caux, key)
    _, cparams, caux = cjit(cparams, cdata, caux,
                            jax.random.fold_in(key, steps + 3))
    _force(cparams)
    tic3 = time.time()
    for i in range(steps):
        _, cparams, caux = cjit(cparams, cdata, caux,
                                jax.random.fold_in(key, i))
    _force(cparams)
    cifar_rate = cbatch * steps / (time.time() - tic3)

    # LSTM language-model tier (round-4 verdict #8): the reference's
    # RNN story is example/rnn/lstm_bucketing.py (PTB: 2x200 LSTM,
    # bptt 35, batch 32, vocab ~10k). Same protocol as the CIFAR
    # tier; metric is words/sec through the fused-scan sym.RNN.
    lstm_rate = _bench_lstm(compute_dtype, steps, key, _force)

    # trace artifact for the winner (round-3 evidence item): a
    # committed-on-round-end summary backs the MFU claims
    import tempfile

    import shutil

    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(tdir)
    for i in range(5):
        outputs, params, aux = jit_step(
            params, data, aux, jax.random.fold_in(key, 500 + i))
    _force(params)
    jax.profiler.stop_trace()
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "tools"))
    from trace_top import aggregate, find_trace_file, load_events

    rows, total_ms = aggregate(
        load_events(find_trace_file(tdir)), steps=5, by_op=False)
    with open(os.path.join(here, ".bench_trace_summary.json"),
              "w") as f:
        json.dump({
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
            "chip": getattr(devices[0], "device_kind",
                            devices[0].platform),
            "layout": layout,
            "batch": batch,
            "device_ms_per_step": round(total_ms, 2),
            "top_ops": [
                {"ms_per_step": round(ms, 2),
                 "share_pct": round(share, 1),
                 "count": n, "op": name}
                for ms, share, n, name in rows[:15]],
        }, f, indent=1)
    shutil.rmtree(tdir, ignore_errors=True)
    step_ms = elapsed / steps * 1000.0
    tflops_model = imgs_per_sec * RESNET50_TRAIN_GFLOPS_PER_IMG / 1e3
    tflops_xla = xla_flops * steps / elapsed / 1e12
    peak = xprof.chip_peak_tflops(devices[0].device_kind)
    result = {
        "metric": "resnet50_train_imgs_per_sec",
        "value": round(imgs_per_sec, 2),
        "unit": "img/s",
        "vs_baseline": round(imgs_per_sec / BASELINE_IMGS_PER_SEC, 3),
        "compute_dtype": dtype_name,
        "batch": batch,
        "layout": layout,
        "step_time_ms": round(step_ms, 2),
        "tflops_model": round(tflops_model, 1),
        "tflops_xla": round(tflops_xla, 1),
        "platform": devices[0].platform,
        "chip": devices[0].device_kind,
        "device_count": len(devices),
        "imgs_per_sec_nhwc": round(nhwc_rate, 1),
        # reference published 842 img/s (1x GTX 980, batch 128)
        "cifar_inception_imgs_per_sec": round(cifar_rate, 1),
        "vs_baseline_cifar": round(cifar_rate / 842.0, 3),
        # the reference publishes no in-tree PTB words/sec; the absolute
        # rate stands on its own (lstm_bucketing.py geometry)
        "lstm_ptb_words_per_sec": round(lstm_rate, 1),
        "mfu_pct": round(100.0 * tflops_model / peak, 1),
        "mfu_pct_xla": round(100.0 * tflops_xla / peak, 1),
    }

    # device observability plane: compile analytics + roofline + HBM
    # watermark. analytic_mfu is MFU from the executable's true FLOP
    # count (cost_analysis) and the measured step time.
    hbm_wm.sample()
    xp = xprof.summary()
    xp["bench_analysis"] = xprof.analyze(
        xla_flops or None, bench_rec.bytes_accessed,
        step_time_s=elapsed / steps,
        device_kind=devices[0].device_kind)
    result["compile_time_s"] = round(compile_time_s, 3)
    if "analytic_mfu_pct" in xp["bench_analysis"]:
        result["analytic_mfu"] = xp["bench_analysis"]["analytic_mfu_pct"]
    result["peak_hbm_bytes"] = int(hbm_wm.peak)
    result["xprof"] = xp

    rec_env = _env.get("MXNET_TPU_BENCH_INPUT")
    if rec_env:
        result.update(_bench_recordio(jit_step, params, aux, key, batch,
                                      image, num_classes, steps, rec_env,
                                      _force, layout=layout))

    # fused-train-step probe: MXNET_TPU_FUSED_STEP rides the child's
    # inherited env, so `MXNET_TPU_FUSED_STEP=1 python bench.py` emits a
    # record self-labeled with the mode AND the measured dispatch count
    # behind it (expect ~1.0 fused vs 3+ classic)
    result["fused"] = _env.get("MXNET_TPU_FUSED_STEP")
    result["dispatches_per_step"] = _bench_fused_dispatch()

    # framework-side counters/spans for this run (engine, io, executor,
    # kvstore, bench.step span stats) ride along in the perf record
    result["telemetry"] = telemetry.snapshot()
    # ... and any anomaly events the step-trace detectors raised, so a
    # recompile-tainted or stall-tainted number is self-labeled
    events = list(tracing.step_trace().events)
    if events:
        result["anomaly_events"] = events

    print(json.dumps(result))


if __name__ == "__main__":
    main()
