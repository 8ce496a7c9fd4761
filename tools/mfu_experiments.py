"""Measured MFU experiments for the ResNet-50 training step (round-3
perf item: experiments, not estimates).

Variants, each timed with the same protocol as bench.py (donated
buffers, two warmup steps, block_until_ready fence):

  baseline  NCHW tower (what bench.py measures)
  nhwc      channels-last tower (models.get_resnet50(layout="NHWC")):
            candidates channels onto the TPU lane axis
  s2d       space-to-depth stem: host-free 2x2 depth-to-space reshape of
            the input to (N, 12, H/2, W/2) + a 5x5/1 stem conv replacing
            7x7/2 — structurally the MLPerf trick (measures the
            throughput effect; not weight-exact with the 7x7 stem)
  nhwc_s2d  both together: channels-last tower + s2d stem
  flags:... any variant re-run under an XLA_FLAGS setting (process
            re-exec; flags only apply at backend init)

Usage:
  python tools/mfu_experiments.py                  # all variants
  python tools/mfu_experiments.py --variant nhwc
  python tools/mfu_experiments.py --sweep-flags \
      "--xla_tpu_enable_latency_hiding_scheduler=true" ...

Prints one JSON line per measurement:
  {"experiment": "nhwc", "imgs_per_sec": N, "step_time_ms": N,
   "mfu_pct": N, "chip": "...", "xla_flags": "..."}

Each line is self-contained evidence for docs/performance.md.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RESNET50_TRAIN_GFLOPS_PER_IMG = 4.089 * 3


def _chip_peak(kind):
    from mxnet_tpu.xprof import chip_peak_tflops

    return chip_peak_tflops(kind)


def validate(result):
    """Physical-plausibility gate for a measurement row. Returns None
    when the row could be real, else a reason string.

    Two invariants no correct measurement can break: model FLOP
    utilization cannot exceed the chip's peak (mfu_pct <= 100), and a
    ResNet-50 train step cannot finish faster than the analytic floor
    ``batch * 12.267 GFLOP / peak`` — the time the chip would need at
    100% utilization. Rows that break either (the 2026-07-31 pre-fence
    lines: 1.46 ms "steps" for batch-256, mfu 1095%) measured dispatch
    latency, not training."""
    mfu = result.get("mfu_pct")
    if mfu is not None and mfu > 100.0:
        return "mfu_pct %.1f exceeds 100%% of chip peak" % mfu
    batch = result.get("batch")
    step_ms = result.get("step_time_ms")
    image = result.get("image", 0)
    # rows measured through the xprof registry carry the compiled
    # executable's true FLOP count: the tightest possible analytic
    # floor, valid for every variant/geometry (not just 224px ResNet)
    flops = result.get("flops_per_step")
    if step_ms and flops:
        try:
            peak = _chip_peak(result.get("chip", ""))
        except Exception:
            peak = None
        if peak:
            floor_ms = flops / (peak * 1e9)
            if step_ms < floor_ms:
                return ("step_time_ms %.2f below executable FLOP floor "
                        "%.2f ms (%.1f GFLOP/step at %.0f peak TFLOPS)"
                        % (step_ms, floor_ms, flops / 1e9, peak))
    if batch and step_ms and image >= 224:
        try:
            peak = _chip_peak(result.get("chip", ""))
        except Exception:
            peak = None
        if peak:
            floor_ms = batch * RESNET50_TRAIN_GFLOPS_PER_IMG / peak
            if step_ms < floor_ms:
                return ("step_time_ms %.2f below analytic floor %.2f ms "
                        "(batch %d ResNet-50 train at %.0f peak TFLOPS)"
                        % (step_ms, floor_ms, batch, peak))
    return None


def retag(path):
    """Rewrite a results .jsonl, tagging physically impossible rows that
    carry no ``valid`` field with ``"valid": false`` + the reason.
    Already-tagged rows and plausible rows pass through byte-identical.
    Returns the number of rows tagged."""
    out, tagged = [], 0
    with open(path) as f:
        for line in f:
            line = line.rstrip("\n")
            if not line.strip():
                continue
            try:
                row = json.loads(line)
            except ValueError:
                out.append(line)
                continue
            if isinstance(row, dict) and "valid" not in row:
                reason = validate(row)
                if reason:
                    row["valid"] = False
                    row["invalid_reason"] = reason
                    line = json.dumps(row)
                    tagged += 1
            out.append(line)
    with open(path, "w") as f:
        for line in out:
            f.write(line + "\n")
    return tagged


def build_variant(variant, batch, image, num_classes, small):
    from mxnet_tpu import models

    layout = "NHWC" if variant in ("nhwc", "nhwc_s2d") else "NCHW"
    if variant in ("s2d", "nhwc_s2d"):
        net = models.get_resnet(
            [3, 4, 6, 3], [64, 256, 512, 1024, 2048],
            num_classes=num_classes, small_input=small, stem_s2d=True,
            layout=layout)
        if layout == "NHWC":
            data_shape = (batch, image // 2, image // 2, 12)
        else:
            data_shape = (batch, 12, image // 2, image // 2)
    else:
        net = models.get_resnet50(num_classes=num_classes,
                                  small_input=small, layout=layout)
        if layout == "NHWC":
            data_shape = (batch, image, image, 3)
        else:
            data_shape = (batch, 3, image, image)
    return net, data_shape


def measure(variant, batch, image, num_classes, steps, dtype_name):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.parallel import build_sgd_train_step

    small = image <= 64
    net, data_shape = build_variant(variant, batch, image, num_classes,
                                    small)
    arg_shapes, _, aux_shapes = net.infer_shape(data=data_shape)
    rng = np.random.RandomState(0)
    params, data = {}, {}
    for name, shape in zip(net.list_arguments(), arg_shapes):
        if name == "data":
            data[name] = jnp.asarray(rng.rand(*shape), jnp.float32)
        elif name == "softmax_label":
            data[name] = jnp.asarray(
                rng.randint(0, num_classes, shape), jnp.float32)
        elif name.endswith("gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            params[name] = jnp.asarray(rng.randn(*shape) * 0.05,
                                       jnp.float32)
    aux = [jnp.ones(s, jnp.float32) if "var" in n
           else jnp.zeros(s, jnp.float32)
           for n, s in zip(net.list_auxiliary_states(), aux_shapes)]

    compute_dtype = None if dtype_name == "float32" \
        else getattr(jnp, dtype_name)
    step, _ = build_sgd_train_step(net, ["data"], ["softmax_label"],
                                   lr=0.01, compute_dtype=compute_dtype)
    jit_step = jax.jit(step, donate_argnums=(0, 2))
    key = jax.random.PRNGKey(0)

    # AOT-compile through the xprof registry: the row carries the
    # executable's true FLOP count (validate() turns it into the
    # analytic floor) and the measured executable is what we dispatch,
    # so the instrumentation never pays the compile twice
    from mxnet_tpu import xprof

    tic_c = time.time()
    step_fn = jit_step.lower(params, data, aux, key).compile()
    compile_time_s = time.time() - tic_c
    flops_per_step = xprof.record_compile(
        "mfu_experiments.%s" % variant, step_fn, compile_time_s).flops

    def _force(tree):
        # fetch a scalar: block_until_ready alone can under-synchronize
        # through remote-device transports, inflating throughput by
        # orders of magnitude (same fence as bench.py — the 2026-07-31
        # pre-fix numbers in MFU_EXPERIMENTS.jsonl show the failure mode:
        # 1.46 ms "steps" for batch-256 ResNet-50)
        leaf = next(iter(tree.values())) if isinstance(tree, dict) else tree
        return float(np.asarray(leaf.sum()))

    outputs, params, aux = step_fn(params, data, aux, key)
    outputs, params, aux = step_fn(params, data, aux,
                                   jax.random.fold_in(key, 999))
    _force(params)
    tic = time.time()
    for i in range(steps):
        outputs, params, aux = step_fn(params, data, aux,
                                       jax.random.fold_in(key, i))
    _force(params)
    elapsed = time.time() - tic

    dev = jax.devices()[0]
    imgs = batch * steps / elapsed
    result = {
        "experiment": variant,
        "imgs_per_sec": round(imgs, 1),
        "step_time_ms": round(elapsed / steps * 1000, 2),
        "batch": batch,
        "image": image,
        "compute_dtype": dtype_name,
        "chip": getattr(dev, "device_kind", dev.platform),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        # marks results produced with the scalar-fetch fence; earlier
        # lines without this field under-synchronized and are invalid
        "fence": "scalar_fetch",
    }
    result["compile_time_s"] = round(compile_time_s, 3)
    if flops_per_step:
        result["flops_per_step"] = flops_per_step
    peak = _chip_peak(getattr(dev, "device_kind", "")) \
        if dev.platform != "cpu" else None
    if peak and image >= 224:
        tflops = imgs * RESNET50_TRAIN_GFLOPS_PER_IMG / 1e3
        result["mfu_pct"] = round(100.0 * tflops / peak, 1)
    if peak and flops_per_step:
        # MFU from the executable's true FLOP count (the analytic
        # number the gap report compares the model-FLOP mfu_pct to)
        result["mfu_pct_xla"] = round(
            100.0 * flops_per_step * steps / elapsed / (peak * 1e12), 1)
    reason = validate(result)
    if reason:
        result["valid"] = False
        result["invalid_reason"] = reason
    return result


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--variant", default="all",
                   choices=["all", "baseline", "nhwc", "s2d",
                            "nhwc_s2d"])
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--image", type=int, default=None)
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--dtype", default=None)
    p.add_argument("--sweep-flags", nargs="*", default=None,
                   help="XLA_FLAGS sweep entries; each entry re-runs "
                        "the chosen variant in a fresh process. Values "
                        "start with '--', which argparse rejects as "
                        "positional — use the '=' form. Commas separate "
                        "INDEPENDENT entries "
                        "(--sweep-flags=--flag1,--flag2 sweeps each "
                        "alone); spaces inside one shell-quoted value "
                        "compose a combined set "
                        "(--sweep-flags='--flag1 --flag2')")
    p.add_argument("--retag", metavar="PATH",
                   help="rewrite an existing results .jsonl, tagging "
                        "physically impossible untagged rows with "
                        "\"valid\": false, then exit")
    p.add_argument("--_child", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.retag:
        n = retag(args.retag)
        sys.stderr.write("mfu_experiments: tagged %d row(s) invalid in %s\n"
                         % (n, args.retag))
        return n

    if args.sweep_flags is not None and not args._child:
        sweep_variants = [args.variant] if args.variant != "all" \
            else ["baseline", "nhwc", "s2d", "nhwc_s2d"]
        # commas separate independent sweep entries; split only on
        # commas that start the NEXT flag — a flag's own value may
        # contain commas (--xla_disable_hlo_passes=a,b)
        flag_sets = [x for f in args.sweep_flags
                     for x in re.split(r",(?=--)", f)]
        for flags in [""] + flag_sets:
            env = dict(os.environ)
            if flags:
                env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " "
                                    + flags).strip()
            for variant in sweep_variants:
                cmd = [sys.executable, os.path.abspath(__file__),
                       "--_child", "--variant", variant]
                for k in ("batch", "image", "steps", "dtype"):
                    v = getattr(args, k)
                    if v is not None:
                        cmd += ["--%s" % k, str(v)]
                r = subprocess.run(cmd, env=env)
                if r.returncode != 0:
                    print(json.dumps({"experiment": variant,
                                      "xla_flags": flags,
                                      "error": "child exited %d"
                                               % r.returncode}))
        return

    import jax
    on_accel = jax.devices()[0].platform != "cpu"
    batch = args.batch or (256 if on_accel else 4)
    image = args.image or (224 if on_accel else 32)
    steps = args.steps or (20 if on_accel else 2)
    dtype = args.dtype or ("bfloat16" if on_accel else "float32")
    num_classes = 1000 if on_accel else 8

    variants = [args.variant] if args.variant != "all" \
        else ["baseline", "nhwc", "s2d", "nhwc_s2d"]
    results = []
    for v in variants:
        r = measure(v, batch, image, num_classes, steps, dtype)
        if r.get("valid") is False:
            # stdout is what callers append to MFU_EXPERIMENTS.jsonl;
            # a physically impossible measurement is evidence of a broken
            # fence, not of performance — refuse to record it
            sys.stderr.write(
                "mfu_experiments: REFUSING to record physically "
                "impossible row (%s): %s\n"
                % (r["invalid_reason"], json.dumps(r)))
        else:
            print(json.dumps(r))
        results.append(r)
    return results


if __name__ == "__main__":
    main()
