"""Driver ``fit_lm``: one ``Module.fit`` call of a language model, timed on
whole steady steps. ``drivers/fit.py`` with what is image- or SGD-shaped
replaced: the window, the spans, the compile count and the traced stretch
are its own (``Window``); here are the token batches, Adam's readings, the
language model's reference (``reference/<net>.py``: ``nemotron_h``) and the
device time by scope.

A sample is a sequence: ``train_samples_per_s`` = sequences a step x whole
steps / fenced seconds; tokens/s is printed beside it. The loss is the mean
cross-entropy over tokens (the metric counts tokens).

``correct`` compares, each beside its limit (``limits/<workload>.json``):
the three first losses; the step-1 log-probabilities at ``check_positions``
seeded positions of each sequence against float32, with the reference at
the stated precision as the floor (``step1_excess_noise``); the first
gradient's norm (from Adam's first moment after step 1: ``g = m1 / (1 -
beta1) - wd w0``) and the three-step change's norm by leaf, every held
expert's slice of the stacked weights a leaf of its own; dead leaves; and
``window_loss_over_first_loss``.

The reference's ``init_params`` gives ONE dictionary of everything made from
the seed; what the program holds as auxiliary states (the experts' selection
biases, which the step moves itself: ``reference.STATE``) goes to ``fit`` as
``aux_params``, the rest as ``arg_params``, and the states' three-step change
is compared by leaf like a parameter's. With ``init.balance`` in the
configuration (``{"from", "to", "steps", "hold"}``, the rates of
``reference.balanced_start``) the biases start where the family's balancing
rule settles on the ring's last batch, so that every seed's experts are
loaded evenly from step 1, as a model in training holds them; the step then
keeps them so (the model's ``bias_update_rate``).

``run(..., controls=(...))`` (``tools/readings_lm.py``) also reads the
reference's controls on the same weights and batch.
"""
import gc
import os
import shutil
import time

import numpy as np

from benchmark import harness
from benchmark.drivers import fit
from benchmark.reference import check, train
from benchmark.trace import reduce as trace_reduce
from benchmark.trace import scopes, xplane

MOE_COUNTERS = ("moe.rows_here", "moe.rows_total", "moe.dropped_rows")
LOWERINGS = ("lower.scan_kernel.xla_chunked",
             "lower.attention_kernel.xla_blockwise",
             "lower.attention_kernel.pallas_splash")


def _leaf_norms(names, arrays, scale=1.0, minus=None):
    """Norms by leaf of ``scale * arrays - minus``, every expert's slice
    of a stacked expert weight a leaf of its own (``reference.leaves``),
    computed in ONE program where the arrays are: the host would take
    minutes over 667 M parameters, a program a leaf tens of seconds of
    small compiles."""
    import jax
    import jax.numpy as jnp

    def stacked(name):
        return name.endswith("experts_up_weight") or name.endswith(
            "experts_down_weight")

    @jax.jit
    def norms(arrays, minus):
        out = []
        for name, a, m in zip(names, arrays, minus):
            d = scale * a.astype(jnp.float32) - (0.0 if m is None else m)
            out.append(jnp.sqrt(jnp.sum(
                jnp.square(d), axis=(1, 2) if stacked(name) else None)))
        return out

    got = norms(list(arrays), list(minus or [None] * len(names)))
    out = {}
    for name, n in zip(names, got):
        n = np.asarray(n, np.float64)
        if stacked(name):
            out.update({"%s[%d]" % (name, j): float(v)
                        for j, v in enumerate(n)})
        else:
            out[name] = float(n)
    return out


class Window(fit.Window):
    """``fit.Window`` over token batches and Adam."""

    def __init__(self, *args, tokens, rows, **kw):
        super().__init__(*args, **kw)
        self.tokens = tokens             # positions a step
        self.rows = rows                 # flat positions compared
        self.expert_rows = []            # the experts' counts, each fence

    def fence(self):
        """As ``fit.Window.fence``; the sum is cumulative mean loss x
        tokens (the metric counts tokens, not sequences)."""
        import jax

        ex = self.mod._exec_group.executor
        jax.block_until_ready([a._data for a in ex.arg_arrays])
        _, mean = self.metric.get()
        return float(mean) * self.k * self.tokens

    def _counters(self):
        # a fence: what the experts counted on the device becomes
        # telemetry here, never inside a step (a no-op with telemetry
        # off, or in a program that has no such counters)
        publish = getattr(self.mod, "publish_aux_counters", None)
        if publish is not None:
            publish()
        aux = self.mod._exec_group.executor.aux_dict
        self.expert_rows.append({n: np.asarray(a._data, np.int64)
                                 for n, a in sorted(aux.items())
                                 if n.endswith("expert_rows")})
        snap = super()._counters()
        for name in MOE_COUNTERS + LOWERINGS:
            snap[name] = self.tel.peek(name) or 0
        snap["moe.expert_load_max_over_mean"] = self.tel.peek(
            "moe.expert_load_max_over_mean", "gauge") or 0.0
        snap["step.fused_fallback"] = self.tel.peek(
            "step.fused_fallback") or 0
        return snap

    def _follow(self, k):
        import jax.numpy as jnp

        loss_sum = self.fence()
        self.got["losses"].append((loss_sum - self.loss_rows) / self.tokens)
        self.loss_rows = loss_sum
        ex = self.mod._exec_group.executor
        names = self.mod._param_names
        if k == 1:
            prob = self.mod.get_outputs()[0]._data
            rows = np.asarray(prob[jnp.asarray(self.rows)], np.float64)
            self.got["logprob"] = np.log(rows + 1e-30)
            # Adam from a zero state: m1 = (1 - b1) (g + wd w0)
            b1, wd = self.recipe.get("beta1", 0.9), self.recipe.get("wd", 0.0)
            states = self.mod._updater.states
            self.got["grad_norms"] = _leaf_norms(
                names, [states[i][0]._data for i in range(len(names))],
                scale=1.0 / (1.0 - b1),
                minus=[wd * jnp.asarray(self.w0[n]) for n in names]
                if wd else None)
        if k == fit.FOLLOW_STEPS:
            # the states the step moves itself (the experts' selection
            # biases) beside the parameters: one that never moved is dead
            states = [n for n in ex.aux_dict if n in self.w0]
            self.got["delta_norms"] = _leaf_norms(
                names + states,
                [ex.arg_dict[n]._data for n in names]
                + [ex.aux_dict[n]._data for n in states],
                minus=[jnp.asarray(self.w0[n]) for n in names + states])
            self.w0 = None


def balance_rates(spec):
    """``{"from", "to", "steps", "hold"}`` -> the rates ``balanced_start``
    runs at: ``steps`` falling geometrically, then ``hold`` at the last."""
    return np.concatenate([
        np.geomspace(spec["from"], spec["to"], spec["steps"]),
        np.full(spec["hold"], spec["to"])]).astype(np.float32)


def check_rows(seed, batch, seq_len, n):
    """``n`` seeded positions of each sequence, as flat rows of the
    ``[batch * seq_len, vocab]`` output and as ``[batch, n]``."""
    rng = np.random.default_rng([int(seed), 7])
    per_seq = np.stack([np.sort(rng.choice(seq_len, size=n, replace=False))
                        for _ in range(batch)])
    flat = (per_seq + seq_len * np.arange(batch)[:, None]).reshape(-1)
    return flat.astype(np.int32), per_seq.astype(np.int32)


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
    flat_rows, seq_rows = check_rows(seed, batch, seq_len,
                                     config["check_positions"])
    # the weights are made on the device in one call, kept on the host
    # (the comparison's w0, and what the reference starts from) and handed
    # to fit from there: a second copy on the chip would take a sixth of it
    source = harness.load_by_name(
        "generators", traffic["generator"]).Source(
            traffic["params"], config, seed, devices)
    with jax.default_device(devices[0]):
        params0 = ref.init_params(ref_args, train.seed_key(seed),
                                  tuple(config["init"]["time_step"]))
        fit.mark(t_start, "weights and traffic made from the seed")
        balance = config["init"].get("balance")
        if balance:
            # training starts where the family's balancing rule settles on
            # the ring's last batch, the one "before" step 1's
            # (reference.balanced_start): experts loaded evenly, as a model
            # in training holds them
            bias, load = ref.balanced_start(
                ref_args, params0, source.last()[0],
                config["compute_dtype"], balance_rates(balance))
            params0.update(bias)
            print("balanced start: rows of the ring's last batch by expert, "
                  "largest / mean by layer: %s" % "  ".join(
                      "%d / %.0f" % (v.max(), v.mean())
                      for _, v in sorted(load.items())))
            fit.mark(t_start, "the experts' selection biases balanced")
        w0 = {k: np.asarray(v) for k, v in params0.items()}
    del params0
    ctx = [mx.tpu(i) if d.platform == "tpu" else mx.cpu(i)
           for i, d in enumerate(devices)]
    net = factory(**config["model"]["args"])
    mod = mx.mod.Module(net, context=ctx)
    metric = mx.metric.create(config["fit"]["eval_metric"])
    telemetry.reset()
    if trace:
        telemetry.enable()
    window = Window(source, mod, metric, recipe, w0, seconds, trace, t_start,
                    compiles, telemetry, tokens=tokens, rows=flat_rows)
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
    on_chip = [a for a in jax.live_arrays()
               if devices[0] in a.devices() and not a.is_deleted()]
    print("memory: peak in use %d + peak reserved %d of %d bytes; in use "
          "now %d; %d live arrays on the chip hold %d" % (
              in_use, reserved, now.get("bytes_limit", 0),
              now.get("bytes_in_use", 0), len(on_chip),
              sum(a.nbytes for a in on_chip)))
    largest = sorted({(a.nbytes, str(a.shape)) for a in on_chip})[-4:]
    print("memory: largest live shapes %s" % ", ".join(
        "%s x%d" % (shape, sum(1 for a in on_chip if str(a.shape) == shape))
        for _, shape in largest))
    window_s = window.t_close - window.t_open
    rate = window.steps * batch / window_s
    print("window: %d steps of %d sequences (%d tokens) in %.4f s: %.4f "
          "sequences/s, %.1f tokens/s, %.2f ms a step; set-up %.2f s (%d "
          "programs built, %d read from the cache)" % (
              window.steps, batch, tokens, window_s, rate,
              window.steps * tokens / window_s,
              1e3 * window_s / window.steps, window.setup_s,
              window.setup_compiles, window.setup_cache_hits))
    first, last = window.expert_rows[0], window.expert_rows[-1]
    held = slice(ref_args["first_expert"],
                 ref_args["first_expert"] + ref_args["experts_held"])
    per_step = {n: (last[n] - first[n])[:-1] / window.steps for n in first}
    if per_step:
        print("experts: rows a step that landed here, by layer: %s = %.0f "
              "(an even share %.0f); largest held expert over the mean of "
              "all: %s" % (
                  " + ".join("%.0f" % v[held].sum()
                             for v in per_step.values()),
                  sum(v[held].sum() for v in per_step.values()),
                  sum(v.sum() for v in per_step.values())
                  * ref_args["experts_held"] / ref_args["experts_total"],
                  " ".join("%.2f" % (v[held].max() / v.mean())
                           for v in per_step.values())))
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
    # collector has not reached) would hold 8 GB
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
        print("control %-16s seed %d  step1_excess_noise %.6g  limit %.6g  "
              "%s  (rms gap to float32 %.5f, the stated precision's own "
              "%.5f, %.1f s)" % (
                  prec, seed, value, limits["step1_excess_noise"],
                  "fails, as it must"
                  if value > limits["step1_excess_noise"] else "PASSES",
                  g, f, time.perf_counter() - t0), flush=True)
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


def part_of(pattern):
    """Which part of the step a scope's (phase, op, node) belongs to: the
    layer's kind by its index in ``pattern``."""
    import re

    layer = re.compile(r"layer(\d+)_")

    def part(phase, op, node):
        if phase == "update":
            return "optimizer"
        if phase == "metric":
            return "lm_head_loss"
        m = layer.match(node)
        if m and int(m.group(1)) < len(pattern):
            kind = pattern[int(m.group(1))]
            if kind == "M":
                return "ssm_scan" if op == "SSMScan" else "ssm_proj_conv"
            if kind == "E":
                return "moe_grouped_matmul" if op == "RoutedExperts" \
                    else "moe_rest"
            return "attention_kernel" if op == "CausalAttention" \
                else "attention_proj"
        if node in ("lm_head", "softmax", "final_norm"):
            return "lm_head_loss"
        return "other:" + (op or phase or "?")
    return part


def per_layer(cell, window, device, memory, peaks, ref, ref_args, batch):
    """The traced stretch reduced, device time by scope added, and each
    per-layer metric read by its own reader."""
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
                                     part_of(ref_args["pattern"]))
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
    counters["moe.expert_load_max_over_mean"] = \
        tr["c1"]["moe.expert_load_max_over_mean"]
    counters["setup_compiles"] = window.setup_compiles
    counters["setup_cache_hits"] = window.setup_cache_hits
    counters["window_compiles"] = \
        window.c_close["jax.compiles"] - window.c_open["jax.compiles"] \
        + window.c_close["executor.jit_build"] \
        - window.c_open["executor.jit_build"] \
        + window.c_close["step.fused_recompiles"] \
        - window.c_open["step.fused_recompiles"]
    for key in LOWERINGS:
        if window.c_close[key]:
            print("lowering: %s traced %d times" % (key, window.c_close[key]))
    spans = window.spans.done[tr["span0"]:tr["span1"]]
    import jax.numpy as jnp

    rows_here = counters["moe.rows_here"] // steps \
        if counters["moe.rows_here"] else None
    cost = ref.step_cost(ref_args, batch,
                         jnp.dtype(cell["config"]["compute_dtype"]).itemsize,
                         rows_here)
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
