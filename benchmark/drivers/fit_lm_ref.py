"""Driver ``fit_lm_ref``: ``fit_lm``'s run of a language model through one
``Module.fit`` call, with everything model-shaped asked of the
configuration's reference file, so that a second language model is new
files only. The window, the token batches, Adam's readings and the
comparison are ``fit_lm``'s own (``Window``, ``check_rows``,
``_leaf_norms``, imported); ``fit_lm.py`` itself reads one model's
arguments (its experts, its pattern's letters, its ``init.time_step``) and
stays that model's driver until a ``benchmark`` issue folds the two.

**Adding a language model** (worked example: ``olmo_hybrid``, PR 30).
Files: the program's factory (``mxnet_tpu/models/<model>.py``);
``configs/<config>.json`` with ``driver: fit_lm_ref``, ``model``:
``{"factory", "args"}`` (``args`` holds ``vocab``, which the
``resident_tokens`` generator reads), ``reference``: ``{"net": <file under
reference/>, "args"}``, ``tokens``: ``{"batch", "seq_len"}``,
``check_positions``, ``compute_dtype``, ``env``, ``fit`` and ``init``
(handed to the reference whole); ``limits/<workload>.json`` (read the
numbers first: ``tools/readings_lm_ref.py``); a reader a new part's metric
(``metrics/<name>.py`` over ``scopes.part_ms`` / ``part_roofline``) and the
entries in ``BENCHMARK.json``. The reference file ``reference/<net>.py``
is plain ``jax.numpy`` and gives:

* ``init_params(args, key, init)``: every parameter AND every auxiliary
  state the step moves itself, float32, from the key in one call; what is
  no argument of the program's symbol goes to ``fit`` as ``aux_params``,
  and its three-step change is compared by leaf like a parameter's;
* ``follow(args, recipe, params_host, batches, rows)`` -> ``{"losses",
  "grad_norms", "delta_norms", "logprob"}`` (three Adam steps in float32 at
  ``highest`` precision) and ``forward_logprob(args, params_host, ids,
  labels, rows, precision)`` (one forward pass: the stated precision is
  the floor of ``step1_excess_noise``, any other name a control that
  ``run(..., controls=...)`` reads on the same weights and batch);
* ``part_of(args)`` -> ``part(phase, op, node)``: the part of the step a
  named scope belongs to (``trace/scopes.by_part``); the parts the readers
  of the benchmark know keep their names;
* ``step_cost(args, batch, itemsize)`` -> ``{"flops", "bytes", "parts":
  {part: (flops, bytes)}}``: useful operations and least bytes of a step;
* ``LOWERINGS``: the program's lowering counters a traced run prints.

A program without the factory (the parent of the PR that adds the model)
exits at once, before anything is made, as ``fit_lm`` does.
"""
import gc
import os
import shutil
import time

import numpy as np

from benchmark import harness
from benchmark.drivers import fit, fit_lm
from benchmark.reference import check, train
from benchmark.trace import reduce as trace_reduce
from benchmark.trace import scopes, xplane


class Window(fit_lm.Window):
    """``fit_lm.Window`` that also snapshots the reference's lowering
    counters."""

    def __init__(self, *args, lowerings=(), **kw):
        super().__init__(*args, **kw)
        self.lowerings = tuple(lowerings)

    def _counters(self):
        snap = super()._counters()
        for name in self.lowerings:
            snap[name] = self.tel.peek(name) or 0
        return snap


def run(cell, seed, seconds, trace, t_start, controls=()):
    spec, config, traffic = cell["spec"], cell["config"], cell["traffic"]
    name = cell["cell"]["name"]
    stray = sorted(k for k in os.environ if k.startswith("MXNET_TPU_")
                   and k not in config["env"])
    if stray:
        raise SystemExit("benchmark: the environment sets %s, which the "
                         "configuration does not: the cell would measure "
                         "another path" % ", ".join(stray))
    os.environ.update(config["env"])
    devices = harness.require_chips(cell["cell"]["chips"])
    t_process, t_start = t_start, time.perf_counter()
    print("process +%.2f s: JAX has its devices; set-up is timed from here"
          % (t_start - t_process), flush=True)
    peaks = harness.peaks(devices[0].device_kind)
    compiles = fit.CompileCounter()

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    fit.mark(t_start, "the program is imported")
    try:
        factory = fit._factory(config["model"]["factory"])
    except (ImportError, AttributeError) as e:
        # a program without the model (the parent of the PR that adds it)
        # fails here, at once, before anything is made
        raise SystemExit("benchmark: the program has no %s (%s)"
                         % (config["model"]["factory"], e))
    ref = harness.load_by_name("reference", config["reference"]["net"])
    ref_args = config["reference"]["args"]
    batch, seq_len = config["tokens"]["batch"], config["tokens"]["seq_len"]
    tokens = batch * seq_len
    recipe = dict(config["fit"]["optimizer_params"])
    flat_rows, seq_rows = fit_lm.check_rows(seed, batch, seq_len,
                                            config["check_positions"])
    # the weights are made on the device in one call, kept on the host
    # (the comparison's w0, and what the reference starts from) and handed
    # to fit from there: a second copy on the chip would take a sixth of it
    source = harness.load_by_name(
        "generators", traffic["generator"]).Source(
            traffic["params"], config, seed, devices)
    with jax.default_device(devices[0]):
        w0 = {k: np.asarray(v) for k, v in ref.init_params(
            ref_args, train.seed_key(seed), config["init"]).items()}
    fit.mark(t_start, "weights and traffic made from the seed")
    ctx = [mx.tpu(i) if d.platform == "tpu" else mx.cpu(i)
           for i, d in enumerate(devices)]
    net = factory(**config["model"]["args"])
    mod = mx.mod.Module(net, context=ctx)
    metric = mx.metric.create(config["fit"]["eval_metric"])
    telemetry.reset()
    if trace:
        telemetry.enable()
    window = Window(source, mod, metric, recipe, w0, seconds, trace, t_start,
                    compiles, telemetry, tokens=tokens, rows=flat_rows,
                    lowerings=ref.LOWERINGS)
    host = mx.cpu(0)
    args_of = set(net.list_arguments())
    mod.fit(window, eval_metric=metric, kvstore=config["fit"]["kvstore"],
            optimizer=config["fit"]["optimizer"],
            optimizer_params=dict(recipe), initializer=None,
            arg_params={k: mx.nd.array(v, ctx=host) for k, v in w0.items()
                        if k in args_of},
            aux_params={k: mx.nd.array(v, ctx=host) for k, v in w0.items()
                        if k not in args_of},
            allow_missing=False, num_epoch=1)
    if window.state != "closed":
        raise SystemExit("benchmark: fit returned before the window closed")
    telemetry.disable()
    device = harness.device_record(devices)   # before the reference runs
    in_use, reserved = harness.memory_peaks(devices)
    now = devices[0].memory_stats() or {}
    print("memory: peak in use %d + peak reserved %d of %d bytes; in use "
          "now %d" % (in_use, reserved, now.get("bytes_limit", 0),
                      now.get("bytes_in_use", 0)))
    window_s = window.t_close - window.t_open
    rate = window.steps * batch / window_s
    print("window: %d steps of %d sequences (%d tokens) in %.4f s: %.4f "
          "sequences/s, %.1f tokens/s, %.2f ms a step; set-up %.2f s (%d "
          "programs built, %d read from the cache)" % (
              window.steps, batch, tokens, window_s, rate,
              window.steps * tokens / window_s,
              1e3 * window_s / window.steps, window.setup_s,
              window.setup_compiles, window.setup_cache_hits))
    prev = (0, 0.0)
    rates = []
    for done, t in window.chunks[1:]:
        rates.append((done - prev[0]) * batch / (t - prev[1]))
        prev = (done, t)
    print("chunk rates (%d steps each, sequences/s): %s" % (
        fit.CHUNK, " ".join("%.3f" % r for r in rates)))
    fallbacks = window.c_close["step.fused_fallback"]

    # ---- correct -------------------------------------------------------
    limits = cell["limits"]
    rows = list(source.check(window.captured))
    first_loss = window.got["losses"][0]
    window_loss = (window.loss_close - window.loss_open) \
        / (window.steps * tokens)
    finite = np.isfinite(window.loss_close)
    rows.append(("window_loss_over_first_loss",
                 window_loss / first_loss if finite else float("inf"),
                 limits["window_loss_over_first_loss"],
                 "%.4f over the window, %.4f at step 1"
                 % (window_loss, first_loss)))
    batches = [(b.data[0]._data, b.label[0]._data) for b in window.captured]
    source.close()
    window.captured = None
    # the program's state is freed before the reference runs: the float32
    # reference needs the chip to itself. Deleted, not only let go of: a
    # reference anywhere (a closure the engine keeps, a cycle the
    # collector has not reached) would hold gigabytes
    ex = mod._exec_group.executor
    held = [a for n, a in zip(ex.arg_names, ex.arg_arrays)
            if n in mod._param_names]
    held += list(ex.aux_arrays) + list(ex._outputs or [])
    held += [g for g in ex.grad_arrays if g is not None]
    held += jax.tree_util.tree_leaves(
        list(mod._updater.states.values()),
        is_leaf=lambda x: hasattr(x, "_data"))
    for nd in held:
        data = getattr(nd, "_data", None)
        if data is not None and not data.is_deleted():
            data.delete()
    window.mod = mod = ex = held = None
    gc.collect()
    jax.clear_caches()
    t_ref, built = time.perf_counter(), compiles.misses
    want = ref.follow(ref_args, recipe, w0, batches, seq_rows)
    want["logprob_stated"] = ref.forward_logprob(
        ref_args, w0, batches[0][0], batches[0][1], seq_rows,
        config["compute_dtype"])
    print("reference: %d steps followed and one forward pass at the stated "
          "precision in %.2f s (%d programs built)"
          % (fit.FOLLOW_STEPS, time.perf_counter() - t_ref,
             compiles.misses - built))
    rows += check.compare(window.got, want, limits)
    correct = True
    for row_name, value, limit, note in rows:
        ok = value <= limit
        correct &= bool(ok)
        print("check %-36s %.6g  limit %.6g  %s  (%s)"
              % (row_name, value, limit, "ok" if ok else "FAILED", note))
    control_reads = {}
    for prec in controls:
        t0 = time.perf_counter()
        got = ref.forward_logprob(ref_args, w0, batches[0][0], batches[0][1],
                                  seq_rows, prec)
        value, g, f = check.excess_noise(got, want["logprob_stated"],
                                         want["logprob"])
        control_reads[prec] = value
        # a control that overflows reads no number: that fails too
        print("control %-16s seed %d  step1_excess_noise %.6g  limit %.6g  "
              "%s  (rms gap to float32 %.5f, the stated precision's own "
              "%.5f, %.1f s)" % (
                  prec, seed, value, limits["step1_excess_noise"],
                  "PASSES" if value <= limits["step1_excess_noise"]
                  else "fails, as it must", g, f, time.perf_counter() - t0),
              flush=True)
    if fallbacks:
        print("check the fused step fell back %d times: FAILED" % fallbacks)
        correct = False
    failed = 0 if finite else window.steps

    # ---- metrics -------------------------------------------------------
    breakdown = None
    if trace:
        metrics, device, breakdown = per_layer(
            cell, window, device, (in_use, reserved), peaks, ref, ref_args,
            batch)
    else:
        metrics = {"train_samples_per_s": {"value": rate,
                                           "unit": "samples/s"},
                   "setup_s": {"value": window.setup_s, "unit": "s"}}
        keep = set(harness.metric_names(spec, "end_to_end", name))
        metrics = {k: v for k, v in metrics.items() if k in keep}
    harness.last_line(correct and not failed, window.steps, failed, metrics,
                      device, breakdown)
    return {"rows": rows, "got": window.got, "want": want,
            "controls": control_reads}


def per_layer(cell, window, device, memory, peaks, ref, ref_args, batch):
    """The traced stretch reduced, device time by the reference's parts
    added, and each per-layer metric read by its own reader."""
    in_use, reserved = memory
    tr = window.traced
    if tr is None or "t1" not in tr:
        raise SystemExit("benchmark: the window was too short to trace")
    steps = tr["k1"] - tr["k0"]
    loaded = xplane.load(window._trace_dir)
    reduced = trace_reduce.reduce(loaded, steps)
    spans_ns = trace_reduce.host_spans(loaded)
    lo, hi = min(s for _, s, _ in spans_ns), max(e for _, _, e in spans_ns)
    by_part = {}
    try:
        planes = scopes.load(xplane.newest_xplane(window._trace_dir))
        if planes:
            by_part = scopes.by_part(planes[0][1], lo, hi,
                                     ref.part_of(ref_args))
    except Exception as e:          # the metric is left out, the run stands
        print("scopes: not read (%s: %s)" % (type(e).__name__, e))
    shutil.rmtree(window._trace_dir, ignore_errors=True)
    scoped = sum(v for k, v in by_part.items() if k != "(no scope)")
    if scoped:
        reduced["scope_s"] = by_part
        print("device time by scope, ms a step: %s" % "  ".join(
            "%s %.2f" % (k, 1e3 * v / steps)
            for k, v in sorted(by_part.items(), key=lambda kv: -kv[1])))
    counters = {k: tr["c1"][k] - tr["c0"][k] for k in tr["c0"]}
    counters["setup_compiles"] = window.setup_compiles
    counters["setup_cache_hits"] = window.setup_cache_hits
    counters["window_compiles"] = \
        window.c_close["jax.compiles"] - window.c_open["jax.compiles"] \
        + window.c_close["executor.jit_build"] \
        - window.c_open["executor.jit_build"] \
        + window.c_close["step.fused_recompiles"] \
        - window.c_open["step.fused_recompiles"]
    for key in window.lowerings:
        if window.c_close[key]:
            print("lowering: %s traced %d times" % (key, window.c_close[key]))
    spans = window.spans.done[tr["span0"]:tr["span1"]]
    import jax.numpy as jnp

    cost = ref.step_cost(ref_args, batch,
                         jnp.dtype(cell["config"]["compute_dtype"]).itemsize)
    info = {"cell": cell["cell"], "config": cell["config"],
            "traffic": cell["traffic"], "peaks": peaks,
            "chips": device["count"], "batch": batch,
            "step_flops": cost["flops"], "step_bytes": cost["bytes"],
            "step_parts": cost["parts"],
            "memory_peak_in_use_bytes": in_use,
            "memory_peak_reserved_bytes": reserved,
            "traced_seconds": tr["t1"] - tr["t0"]}
    metrics = harness.read_per_layer(cell["spec"], cell["cell"]["name"],
                                     reduced, counters, spans, info)
    device = dict(device, busy_s=reduced["busy_s"],
                  window_s=reduced["window_s"])
    return metrics, device, trace_reduce.breakdown(reduced)
