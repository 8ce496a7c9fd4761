"""Driver ``fit``: one ``Module.fit`` call, timed on whole steady steps.

The traffic mix's generator is wrapped in ``Window``, an iterator that
presents it to ``fit`` as ONE long epoch, so no epoch boundary (a D2H of
every weight) falls inside the window, and that does the harness's work
at the step boundaries ``fit`` calls it on:

* set-up is timed from the moment JAX has its devices (``run``) to the
  opening of the window; the process's way there is printed, not counted;
* steps 1-3: the readings ``correct`` rests on (each step's loss, the
  optimizer's state after step 1, the parameters after step 3) — the
  same module, the same call and the same feed the window then times;
* warm-up: until every shape has compiled and ``WARM_STEPS`` further
  steps have passed; then ``gc.collect()``, a fence (``block_until_ready``
  on the parameters and a scalar fetch of the metric) and the window
  opens. GC stays on inside it;
* the window closes at the first step boundary after ``--seconds``, on
  the same fence, before the epoch is stopped. Only whole steps count;
* ``--trace 1`` profiles about five seconds inside the window, between
  two more fences, and reduces the trace before the process exits.

After ``fit`` returns the reference follows the same three steps from the
same seeded weights and batches (``reference/``), and every number
compared is printed beside its limit.
"""
import gc
import importlib
import os
import shutil
import tempfile
import time

import numpy as np

from benchmark import harness
from benchmark.reference import check, train, walk
from benchmark.trace import reduce as trace_reduce
from benchmark.trace import xplane

FOLLOW_STEPS = 3     # the reference follows this many
WARM_STEPS = 12      # further steps before the window opens
CHUNK = 16           # steps per printed chunk rate
TRACE_SECONDS = 5.0
MIN_TRACED_STEPS = 8


def mark(t_start, what):
    """Where set-up's seconds go, on an earlier line of the output."""
    print("set-up +%.2f s: %s" % (time.perf_counter() - t_start, what),
          flush=True)


class CompileCounter:
    """JAX's own count of programs it had to build: persistent-cache
    misses (built by XLA) and hits (read back), from its monitoring
    events. Nothing of the program is read."""

    MISS = "/jax/compilation_cache/cache_misses"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        from jax import monitoring

        self.misses = self.hits = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kw):
        if event == self.MISS:
            self.misses += 1
        elif event == self.HIT:
            self.hits += 1

    def total(self):
        return self.misses + self.hits


class Spans:
    """The harness's host spans: (name, t0, t1) on the host clock always,
    and the same stretch as a ``TraceAnnotation`` while a trace runs, so
    that idle gaps of the device can be laid against them."""

    def __init__(self):
        self.done = []
        self.tracing = False
        self._open = {}

    def begin(self, name):
        ann = None
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX
                                               + name)
            ann.__enter__()
        self._open[name] = (time.perf_counter(), ann)

    def end(self, name):
        t0, ann = self._open.pop(name, (None, None))
        if t0 is None:
            return
        if ann is not None:
            ann.__exit__(None, None, None)
        self.done.append((name, t0, time.perf_counter()))


class Window:
    """The iterator ``fit`` draws from (see the module's docstring)."""

    def __init__(self, source, module, metric, recipe, w0, seconds, trace,
                 t_start, compiles, telemetry):
        self.source, self.mod, self.metric = source, module, metric
        self.recipe = recipe
        self.w0 = w0                 # the seeded weights, on the host
        self.seconds, self.trace = seconds, trace
        self.t_start, self.compiles, self.tel = t_start, compiles, telemetry
        self.batch_size = source.batch_size
        self.provide_data = source.provide_data
        self.provide_label = source.provide_label
        self.k = 0                   # batches handed out = steps started
        self.open_at = FOLLOW_STEPS + WARM_STEPS
        self.captured = []           # the first steps' batches
        self.got = {"losses": []}    # the program's readings
        self.loss_rows = 0.0
        self.spans = Spans()
        self.h2d_bytes = 0           # handed over from the host
        self.state = "setup"
        self.chunks = []             # (steps, seconds) inside the window
        self.traced = None           # what the traced stretch gave
        self._trace_dir = None

    # -- the DataIter protocol fit uses ---------------------------------
    def reset(self):
        pass                         # one epoch, never rewound

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def next(self):
        spans = self.spans
        spans.end("fit_loop")
        spans.begin("iter.next")
        try:
            self._boundary()
        except StopIteration:
            spans.end("iter.next")
            raise
        spans.begin("input.next")
        batch = self.source.next()
        spans.end("input.next")
        if self.k < FOLLOW_STEPS:
            self.captured.append(batch)
        self.h2d_bytes += self._host_bytes(batch) \
            + self.source.h2d_bytes_inside
        self.k += 1
        spans.end("iter.next")
        spans.begin("fit_loop")
        return batch

    def _host_bytes(self, batch):
        devices = self._devices()
        n = 0
        for arr in list(batch.data) + list(batch.label):
            data = getattr(arr, "_data", arr)
            on = getattr(data, "devices", None)
            if on is None or not set(on()) <= devices:
                n += int(np.prod(arr.shape)) * np.dtype(arr.dtype).itemsize
        return n

    def _devices(self):
        ex = self.mod._exec_group.executor
        return set(ex.arg_arrays[0]._data.devices())

    # -- what happens between two steps ---------------------------------
    def fence(self):
        """Everything dispatched has finished: the parameters are ready
        and the metric's running sum is on the host. Returns that sum
        (cumulative mean loss x rows)."""
        import jax

        ex = self.mod._exec_group.executor
        jax.block_until_ready([a._data for a in ex.arg_arrays])
        _, mean = self.metric.get()
        return float(mean) * self.k * self.batch_size

    def _boundary(self):
        k = self.k
        if self.state == "setup":
            if k == 0:
                mark(self.t_start, "fit has bound, placed the weights and "
                     "asks for the first batch")
            if 1 <= k <= FOLLOW_STEPS:
                self._follow(k)
                mark(self.t_start, "step %d done and read" % k)
            if k == self.open_at:
                self._open()
            return
        now = time.perf_counter()
        done = k - self.k_open
        if done % CHUNK == 0:
            self.chunks.append((done, now - self.t_open))
        if self.trace:
            self._trace_boundary(now)
        if self.state == "open" and now - self.t_open >= self.seconds:
            self._close()
            raise StopIteration

    def _follow(self, k):
        """The program's side of ``correct``, read where fit stands after
        step ``k``."""
        loss_sum = self.fence()
        self.got["losses"].append(
            (loss_sum - self.loss_rows) / self.batch_size)
        self.loss_rows = loss_sum
        ex = self.mod._exec_group.executor
        names = self.mod._param_names
        if k == 1:
            # what the step put out for its batch, at the seeded weights
            prob = self.mod.get_outputs()[0].asnumpy().astype(np.float64)
            self.got["logprob"] = np.log(prob + 1e-30)
            # SGD with momentum from a zero state: m1 = -lr*(g + wd*w0),
            # so the gradient the optimizer got is -m1/lr - wd*w0
            lr, wd = self.recipe["learning_rate"], self.recipe["wd"]
            states = self.mod._updater.states
            self.got["grad_norms"] = {}
            for i, name in enumerate(names):
                # no state after a step: the optimizer got no gradient
                m1 = states[i].asnumpy().astype(np.float64) \
                    if states.get(i) is not None else 0.0
                g = -m1 / lr - wd * self.w0[name].astype(np.float64)
                self.got["grad_norms"][name] = float(np.sqrt((g * g).sum()))
        if k == FOLLOW_STEPS:
            self.got["delta_norms"] = {}
            for name in names:
                d = ex.arg_dict[name].asnumpy().astype(np.float64) \
                    - self.w0[name]
                self.got["delta_norms"][name] = float(np.sqrt((d * d).sum()))
            self.w0 = None

    def _counters(self):
        t = self.tel
        snap = {name: t.peek(name) or 0 for name in (
            "step.dispatches", "step.fused_steps", "step.fused_recompiles",
            "executor.jit_build", "io.batches")}
        snap["io.feed_stall_ms"] = t.peek("io.feed_stall_ms",
                                          "hist_sum") or 0.0
        snap["jax.compiles"] = self.compiles.total()
        snap["h2d_bytes"] = self.h2d_bytes
        snap["steps"] = self.k
        return snap

    def _open(self):
        t0 = time.perf_counter()
        gc.collect()
        self.gc_seconds = time.perf_counter() - t0
        self.loss_open = self.fence()
        self.setup_compiles = self.compiles.misses
        self.setup_cache_hits = self.compiles.hits
        self.c_open = self._counters()
        self.k_open = self.k
        self.state = "open"
        self.t_open = time.perf_counter()
        self.setup_s = self.t_open - self.t_start

    def _close(self):
        self.loss_close = self.fence()
        self.t_close = time.perf_counter()
        self.c_close = self._counters()
        self.steps = self.k - self.k_open
        self.state = "closed"

    def _trace_boundary(self, now):
        import jax

        length = min(TRACE_SECONDS, self.seconds / 2.0)
        if self.state == "open" and self.traced is None \
                and now - self.t_open >= min(1.0, self.seconds / 4.0):
            self.fence()
            self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            options = jax.profiler.ProfileOptions()
            # host spans are our own; the runtime's own host events at
            # level 2 slowed the classic loop to a step a second (PR 23)
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self._trace_dir,
                                     profiler_options=options)
            # the traced stretch runs from here to the fence that ends
            # it, and its spans cover it whole on the trace's clock
            self.spans.end("iter.next")
            self.spans.tracing = True
            self.traced = {"c0": self._counters(), "k0": self.k,
                           "t0": time.perf_counter(),
                           "span0": len(self.spans.done)}
            self.state = "tracing"
            self.spans.begin("iter.next")
        elif self.state == "tracing" \
                and now - self.traced["t0"] >= length \
                and self.k - self.traced["k0"] >= MIN_TRACED_STEPS:
            self.fence()
            self.spans.end("iter.next")
            self.traced.update(t1=time.perf_counter(), k1=self.k,
                               c1=self._counters(),
                               span1=len(self.spans.done))
            jax.profiler.stop_trace()
            self.spans.tracing = False
            self.state = "open"
            self.spans.begin("iter.next")


def _factory(path):
    module, _, attr = path.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def run(cell, seed, seconds, trace, t_start):
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
    # set-up is timed from here: what comes before is Python, ``import
    # jax`` and the TPU runtime's own start, 7-11 s that differ by
    # seconds from process to process with no work of the benchmark or
    # the program in them (PERF.md section 2)
    t_process, t_start = t_start, time.perf_counter()
    print("process +%.2f s: JAX has its devices; set-up is timed from here"
          % (t_start - t_process), flush=True)
    peaks = harness.peaks(devices[0].device_kind)
    compiles = CompileCounter()

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    mark(t_start, "the program is imported")
    batch, chw = config["batch"], tuple(config["input_chw"])
    ref = config["reference"]
    recipe = config["fit"]["optimizer_params"]
    shapes = train.param_shapes(ref["net"], ref["args"], (batch,) + chw)
    params0 = train.init_params(shapes, seed)
    source = harness.load_by_name(
        "generators", traffic["generator"]).Source(
            traffic["params"], config, seed, devices)

    mark(t_start, "weights and traffic made from the seed")
    ctx = [mx.tpu(i) if d.platform == "tpu" else mx.cpu(i)
           for i, d in enumerate(devices)]
    net = _factory(config["model"]["factory"])(**config["model"]["args"])
    mod = mx.mod.Module(net, context=ctx)
    metric = mx.metric.create(config["fit"]["eval_metric"])
    telemetry.reset()
    if trace:
        telemetry.enable()           # the counters the readers take
    window = Window(source, mod, metric, recipe,
                    {k: np.asarray(v) for k, v in params0.items()},
                    seconds, trace, t_start, compiles, telemetry)
    arg_params = {k: mx.nd.NDArray(v) for k, v in params0.items()}
    aux_params = {k: mx.nd.array(np.full(shape, fill, np.float32))
                  for k, (shape, fill) in train.aux_shapes(shapes).items()}
    mod.fit(window, eval_metric=metric, kvstore=config["fit"]["kvstore"],
            optimizer=config["fit"]["optimizer"],
            optimizer_params=dict(recipe), initializer=None,
            arg_params=arg_params, aux_params=aux_params, num_epoch=1)
    if window.state != "closed":
        raise SystemExit("benchmark: fit returned before the window closed")
    telemetry.disable()
    device = harness.device_record(devices)   # before the reference runs
    in_use, reserved = harness.memory_peaks(devices)
    now = devices[0].memory_stats() or {}
    # buffers in use now are no more than the live arrays hold (which
    # count host-side copies too), so the programs' temporaries are not
    # among them: the two peaks add up
    print("memory: peak in use %d + peak reserved %d of %d bytes; in use "
          "now %d, the live arrays hold %d" % (
              in_use, reserved, now.get("bytes_limit", 0),
              now.get("bytes_in_use", 0),
              sum(a.nbytes for a in jax.live_arrays())))

    window_s = window.t_close - window.t_open
    rate = window.steps * batch / window_s
    print("window: %d steps of %d in %.4f s; set-up %.2f s (%d programs "
          "built, %d read from the cache; the collection before the window "
          "took %.2f s)" % (
              window.steps, batch, window_s, window.setup_s,
              window.setup_compiles, window.setup_cache_hits,
              window.gc_seconds))
    prev = (0, 0.0)
    rates = []
    for done, t in window.chunks[1:]:
        rates.append((done - prev[0]) * batch / (t - prev[1]))
        prev = (done, t)
    print("chunk rates (%d steps each, samples/s): %s" % (
        CHUNK, " ".join("%.1f" % r for r in rates)))

    # ---- correct -------------------------------------------------------
    limits = cell["limits"]
    rows = list(source.check(window.captured))
    first_loss = window.got["losses"][0]
    window_loss = (window.loss_close - window.loss_open) \
        / (window.steps * batch)
    finite = np.isfinite(window.loss_close)
    rows.append(("window_loss_over_first_loss",
                 window_loss / first_loss if finite else float("inf"),
                 limits["window_loss_over_first_loss"],
                 "%.4f over the window, %.4f at step 1"
                 % (window_loss, first_loss)))
    batches = [(b.data[0]._data, b.label[0]._data) for b in window.captured]
    source.close()
    window.captured = None
    # the program's state is freed before the reference runs: its
    # executables keep gigabytes reserved for their temporaries (10 GB on
    # the classic loop) and the float32 reference needs 6 GB of its own
    window.mod = mod = None
    gc.collect()
    jax.clear_caches()
    t_ref, built = time.perf_counter(), compiles.misses
    want = follow_reference(config, params0, batches)
    print("reference: %d steps followed and one forward pass at the stated "
          "precision in %.2f s (%d programs built)"
          % (FOLLOW_STEPS, time.perf_counter() - t_ref,
             compiles.misses - built))
    rows += check.compare(window.got, want, limits)
    correct = True
    for row_name, value, limit, note in rows:
        ok = value <= limit
        correct &= bool(ok)
        print("check %-36s %.6g  limit %.6g  %s  (%s)"
              % (row_name, value, limit, "ok" if ok else "FAILED", note))
    failed = 0 if finite else window.steps

    # ---- metrics -------------------------------------------------------
    breakdown = None
    if trace:
        metrics, device, breakdown = per_layer(
            cell, window, device, (in_use, reserved), peaks, ref, batch, chw)
    else:
        metrics = {"train_samples_per_s": {"value": rate,
                                           "unit": "samples/s"},
                   "setup_s": {"value": window.setup_s, "unit": "s"}}
        keep = set(harness.metric_names(spec, "end_to_end", name))
        metrics = {k: v for k, v in metrics.items() if k in keep}
    harness.last_line(correct and not failed, window.steps, failed, metrics,
                      device, breakdown)
    return {"rows": rows, "got": window.got, "want": want}


def follow_reference(config, params0, batches):
    """The reference's readings for the same first steps, and its
    log-probabilities for the first batch at the precision the
    configuration states."""
    ref = config["reference"]
    want = train.follow(ref["net"], ref["args"],
                        config["fit"]["optimizer_params"], params0, batches)
    want["logprob_stated"] = train.forward_logprob(
        ref["net"], ref["args"], params0, batches[0][0],
        precision=config["compute_dtype"])
    return want


def per_layer(cell, window, device, memory, peaks, ref, batch, chw):
    """The traced stretch reduced, and each per-layer metric read from it
    by its own reader."""
    in_use, reserved = memory
    tr = window.traced
    if tr is None or "t1" not in tr:
        raise SystemExit("benchmark: the window was too short to trace")
    steps = tr["k1"] - tr["k0"]
    reduced = trace_reduce.reduce(xplane.load(window._trace_dir), steps)
    shutil.rmtree(window._trace_dir, ignore_errors=True)
    counters = {k: tr["c1"][k] - tr["c0"][k] for k in tr["c0"]}
    counters["setup_compiles"] = window.setup_compiles
    counters["setup_cache_hits"] = window.setup_cache_hits
    counters["window_compiles"] = \
        window.c_close["jax.compiles"] - window.c_open["jax.compiles"] \
        + window.c_close["executor.jit_build"] \
        - window.c_open["executor.jit_build"] \
        + window.c_close["step.fused_recompiles"] \
        - window.c_open["step.fused_recompiles"]
    spans = window.spans.done[tr["span0"]:tr["span1"]]
    import jax.numpy as jnp

    flops, nbytes = walk.step_cost(
        train.load_net(ref["net"]), (batch,) + chw,
        jnp.dtype(cell["config"]["compute_dtype"]).itemsize, **ref["args"])
    info = {"cell": cell["cell"], "config": cell["config"],
            "traffic": cell["traffic"], "peaks": peaks,
            "chips": device["count"], "batch": batch,
            "step_flops": flops, "step_bytes": nbytes,
            "memory_peak_in_use_bytes": in_use,
            "memory_peak_reserved_bytes": reserved,
            "traced_seconds": tr["t1"] - tr["t0"]}
    metrics = harness.read_per_layer(cell["spec"], cell["cell"]["name"],
                                     reduced, counters, spans, info)
    device = dict(device, busy_s=reduced["busy_s"],
                  window_s=reduced["window_s"])
    return metrics, device, trace_reduce.breakdown(reduced)
