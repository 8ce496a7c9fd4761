"""Fused train step: one donated XLA dispatch per training batch.

The classic fit() loop issues three host dispatches per batch —
``forward_backward`` (one fused fwd+bwd computation), ``update`` (one
donated kernel per optimizer structure group) and ``update_metric``
(a fold or an eager ``asnumpy`` sync) — and the gaps between them are
pure host overhead on an accelerator (what the classic loop loses to
them on the chip is not measured: no benchmark cell runs it, PERF.md
section 7 row 3; the fused, resident-fed cells leave the device idle
0.2-0.3% of a step, ledger PR 27). This module compiles the whole batch
into a SINGLE ``jax.jit`` call:

    params', outputs, aux', opt_states', metric_acc' =
        step(params, data/labels, aux, opt_states, hyper_vec, acc, key)

* forward+backward via ``jax.vjp`` through the executor's own
  ``_run_graph`` (same numerics, same mixed-precision casts),
* the optimizer update via the same ``_update_math`` pure functions the
  unfused donated kernels use (hyperparameters ride in traced f32
  matrices, so an LRScheduler never forces a recompile),
* the metric fold via :meth:`EvalMetric.device_fold` into a cumulative
  on-device ``(sum, count)`` accumulator (host fetch only in ``get()``).

Params, aux states, optimizer states and the metric accumulator are
DONATED: XLA writes the new values into the old HBM buffers, so the
step holds one copy of the training state. The data/label buffers are
NOT donated — the caller's batch arrays stay readable after the step.

Data parallelism rides for free: the executor group shards the batch
over its device mesh (GSPMD), so the gradient all-reduce happens inside
this same computation — there is no separate aggregation phase to fuse.

Opt-in via ``MXNET_TPU_FUSED_STEP=1`` — or DEFAULT under a
``device_sync`` kvstore (the in-jit GSPMD gradient exchange: batch
sharded along the ``dp`` mesh axis, params/optimizer state replicated,
and the vjp gradients pinned to a replicated ``NamedSharding`` so the
mean-psum all-reduce runs inside this one dispatch; gate with
``MXNET_TPU_DEVICE_SYNC_FUSED=0``). :func:`make_fused_step` returns
None (-> classic three-phase loop) whenever a precondition fails:
``dist_*`` kvstores, ``update_on_kvstore``, custom-update optimizers
without a fusable plan, grad_req "add", ``inputs_need_grad``, or a
monitor with a custom ``stat_func`` (which needs every internal
tensor; default-stat monitors ride the numwatch stats pack instead —
see ``mxnet_tpu/numwatch.py``). A requested-but-failed precondition
counts ``step.fused_fallback[.reason]`` and warns once naming the
reason.

Telemetry: ``step.dispatches`` counts XLA computation launches per
batch on both paths (the benchmark reads it as
``fit_dispatches_per_step``: 1.0 fused);
``step.fused_recompiles`` counts fresh trace signatures (a shape-driven
recompile storm trips the tracing RecompileDetector);
``step.fused_fallback`` counts requested-but-refused configurations;
``step.update_seam.apart`` / ``.riding`` count, once a traced program, the
parameters whose update follows its weight-gradient product as a pass of
its own and those whose update rides it (:func:`_plan_update_seam`), gauge
``step.update_seam.apart_bytes`` what crosses between them.
"""
from __future__ import annotations

import math

from . import telemetry as _tel
from . import env as _env
from . import xprof as _xprof
from .analysis import sanitizers as _san
from .base import MXNetError
from .engine import get_engine
from .executor import _weight_grad_flops, zero_cotangent

__all__ = ["enabled", "make_fused_step", "FusedTrainStep",
           "make_fused_infer", "FusedInfer"]


def enabled() -> bool:
    """MXNET_TPU_FUSED_STEP=1 requests the fused path (default off)."""
    return _env.get("MXNET_TPU_FUSED_STEP")


_FALLBACK_WARNED = set()


def _fallback(module, reason, detail):
    """A config requested the fused step but a precondition failed: count
    it (`step.fused_fallback` + per-reason key, the trace_report
    `fallbacks` column) and warn ONCE per reason naming what to change —
    the old silent None meant exactly the configs that matter at scale
    quietly ran the three-dispatch loop."""
    _tel.inc("step.fused_fallback")
    _tel.inc("step.fused_fallback." + reason)
    if reason not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(reason)
        import logging

        getattr(module, "logger", logging).warning(
            "fused train step requested but falling back to the classic "
            "three-phase loop: %s [reason=%s]", detail, reason)
    return None


def make_fused_step(module, eval_metric):
    """Build a :class:`FusedTrainStep` for a bound, optimizer-initialized
    Module, or None when any precondition fails (fit() then runs the
    classic forward_backward/update/update_metric loop). The fused path
    is requested by MXNET_TPU_FUSED_STEP=1 — or by default under a
    ``device_sync`` kvstore (in-jit GSPMD gradient exchange; gate with
    MXNET_TPU_DEVICE_SYNC_FUSED=0). A requested-but-failed precondition
    is NOT silent: it counts ``step.fused_fallback[.reason]`` and warns
    once per reason."""
    kv = module._kvstore
    requested = enabled()
    if not requested:
        # device_sync asks for the fused path by contract: its gradient
        # exchange IS the in-jit collective, there is no push/pull round
        # for the classic loop to ride
        requested = (getattr(kv, "in_jit_gradient_exchange", False)
                     and _env.get("MXNET_TPU_DEVICE_SYNC_FUSED"))
    if not requested:
        return None   # not a fallback: fused was never asked for
    if not module.optimizer_initialized or module._update_on_kvstore:
        return _fallback(module, "kvstore_update",
                         "the optimizer update runs on the kvstore "
                         "(dist server-side update), which the fused "
                         "step cannot subsume")
    # inline-dispatch engines only: the write-back closure assigns
    # executor/metric state the fit loop reads right back; a threaded
    # engine would run it on a worker while the loop races ahead
    from .engine import NaiveEngine, XLAEngine

    if type(get_engine()) not in (XLAEngine, NaiveEngine):
        return _fallback(module, "threaded_engine",
                         "a threaded engine is active; the fused step "
                         "needs an inline engine (MXNET_ENGINE_TYPE="
                         "XLAEngine or NaiveEngine)")
    if kv is not None and not getattr(kv, "fused_step_compatible", False):
        # a kvstore that knows WHY it can't fuse names the surviving
        # host path (dist_host_exchange / dist_async_host) so the
        # telemetry points at the actual byte movement, not just "dist"
        reason, detail = getattr(kv, "fused_fallback", None) or (
            "dist_kvstore",
            "kvstore %r moves gradient bytes between dispatches; use a "
            "local/device/device_sync store to fuse" % kv.type)
        return _fallback(module, reason, detail)
    if module.inputs_need_grad:
        return _fallback(module, "inputs_need_grad",
                         "inputs_need_grad=True requires materialized "
                         "input gradients the fused step never builds")
    ex = module._exec_group.executor
    if ex._monitor_callback is not None:
        # a default-stat Monitor is expressible from the numwatch stats
        # pack and rides the fused step (maybe_plane routes it); only a
        # custom stat_func still needs every internal tensor host-side
        from . import numwatch as _numwatch

        mon = getattr(ex._monitor_callback, "__self__", None)
        if not _numwatch.monitor_routable(mon):
            return _fallback(module, "monitor_custom",
                             "an installed monitor with a custom "
                             "stat_func needs every internal tensor; "
                             "the fused step keeps them in-graph "
                             "(default-stat monitors ride the numwatch "
                             "pack)")
    # grad_req "add" accumulates across batches in the grad arrays; the
    # fused step never materializes per-param grads, so it can't honor it
    if any(ex._grad_req[ex.arg_names[i]] != "write" for i in ex._grad_idx):
        return _fallback(module, "grad_req",
                         "grad_req != \"write\" accumulates into grad "
                         "arrays the fused step never materializes")
    opt = module._optimizer
    if not opt._fusable() or not _env.get("MXNET_TPU_FUSED_UPDATE"):
        return _fallback(module, "optimizer",
                         "optimizer %s has no fusable update plan (or "
                         "MXNET_TPU_FUSED_UPDATE=0)"
                         % type(opt).__name__)
    # every grad-bearing arg must map onto an updater slot
    param_idx = {n: i for i, n in enumerate(module._param_names)}
    if any(ex.arg_names[i] not in param_idx for i in ex._grad_idx):
        return _fallback(module, "unmapped_grad_arg",
                         "a grad-bearing arg has no updater slot "
                         "(param list out of sync with the graph)")
    return FusedTrainStep(module, eval_metric)


# The seam between a weight-gradient product and the optimizer's update.
# Left alone XLA fuses a parameter's update onto the product of its
# gradient as an epilogue: three float32 operands read and three results
# written per output tile, beside the product's own. For a small
# parameter that saves the gradient's trip through HBM; for a large dense
# weight whose product the MXU bounds, the epilogue's streams cost the
# product more than the trip. The two constants were measured on the v5e
# alone, in the four language-model cells stepped with every gradient
# apart and with none (PR 39; PERF.md section 6 has the runs): a class of
# weights gained where the product apart saved more than the 0.028-0.037
# ms a million parameters that the update's own pass costs. On another
# chip of ``xprof.CHIP_PEAKS`` the margin follows that chip's peaks and
# the floor is unverified.
#: the product's time at the peak over the update's bytes' time at the HBM
#: rate, from which a product counts as bound by the MXU (an Adam'd dense
#: weight reaches 1.0 at ~2,900 rows contracted, 2.0 at ~5,800; the cells'
#: dense weights sit at 2.84, their experts at 0.13-0.18: the chip has
#: said nothing between, the 2.0 is the TPU compiler's own estimate's)
_SEAM_MXU_MARGIN = 2.0
#: bytes an update moves (weight and states, read and written) from which
#: every class of weights measured gained or broke even apart (four, at
#: 951-1,156 MB); of the thirteen classes at 72-665 MB six gained and
#: seven lost by nothing the step can observe and explain: they ride
_SEAM_FLOOR_BYTES = 768 << 20


def _plan_update_seam(flops, update_bytes, peaks, force=None):
    """Positions of the parameters whose update FOLLOWS their
    weight-gradient product as an elementwise pass of its own, the
    gradient written once between them; every other update RIDES the
    product, as the compiler fuses it. From what can be observed, no
    knob: ``flops[pos]`` the operations of the products that form the
    gradient (``None`` where a reader of the parameter states nothing:
    rides), ``update_bytes[pos]`` what its update reads and writes,
    ``peaks`` the device's ``(TFLOP/s, GB/s)`` (``None`` where it reports
    none, the CPU: everything rides). Apart goes a parameter whose
    product the MXU bounds by :data:`_SEAM_MXU_MARGIN` and whose update
    moves at least :data:`_SEAM_FLOOR_BYTES`. ``force`` is the tests':
    ``"apart"`` / ``"riding"`` for every parameter."""
    if force is not None:
        return frozenset(range(len(flops)) if force == "apart" else ())
    if peaks is None:
        return frozenset()
    tflops, gbps = peaks
    return frozenset(
        pos for pos, (f, b) in enumerate(zip(flops, update_bytes))
        if f is not None and b >= _SEAM_FLOOR_BYTES
        and f / (tflops * 1e12) >= _SEAM_MXU_MARGIN * b / (gbps * 1e9))


# The step's outputs and the update. The parameters are donated: an
# update is written where its parameter stood. Short of memory, XLA's
# rematerialisation defers a large output to the program's end and
# recomputes it there from whatever it still finds, and what it finds of
# a donated parameter is the UPDATED one: in the Ling cell (PR 40) the
# float32 probabilities ``[8192, 19648]`` came out of the head's product
# run again AFTER the head's Adam update (``fusion.2840.remat`` reading
# ``lm_head_weight`` 925 instructions behind the ``divide_subtract_fusion``
# that had overwritten it), and the loss the metric folds from them read
# 0.57% low at a rate of 3e-5, by the rate (gradients and parameters were
# right: they read the forward pass's own probabilities). The compiler is
# told what it may not assume: the parameters that the outputs' nearest
# nodes read go to their update through ONE optimization barrier together
# with the outputs, so those updates wait for outputs that are complete.
#: bytes of outputs from which they are tied: what rematerialisation
#: defers is what frees memory worth a product run again (644 MB there);
#: the image cells' 1 MB of probabilities and every toy program stay as
#: they were
_OUTPUTS_FLOOR_BYTES = 64 << 20


def _outputs_nearest_parameters(symbol, params):
    """The names among ``params`` that the outputs' nearest nodes read:
    from every output back along its inputs, each path as far as the first
    node that reads any of ``params`` (a language model's head; a
    classifier's last ``FullyConnected``: weight and bias)."""
    found, seen = set(), set()
    stack = [node for node, _ in symbol._outputs]
    while stack:
        node = stack.pop()
        if node.uid in seen or node.is_variable:
            continue
        seen.add(node.uid)
        read = {src.name for src, _ in node.inputs
                if src.is_variable and src.name in params}
        if read:
            found |= read
        else:
            stack.extend(src for src, _ in node.inputs)
    return found


class FusedTrainStep:
    """One-dispatch training step bound to a Module's executor group.

    Host work per batch is only what CANNOT trace: ``load_data_batch``
    (H2D), the optimizer's per-step plan (update counts, lr schedule —
    plans must not read the gradient, which never exists host-side
    here), and the engine push of the write-back closure.
    """

    def __init__(self, module, eval_metric):
        self._module = module
        self._group = module._exec_group
        self._executor = ex = self._group.executor
        self._optimizer = module._optimizer
        self._updater = module._updater

        param_idx = {n: i for i, n in enumerate(module._param_names)}
        self._p_arg_idx = list(ex._grad_idx)
        in_p = set(self._p_arg_idx)
        self._o_arg_idx = [i for i in range(len(ex.arg_names))
                           if i not in in_p]
        self._p_upd_idx = [param_idx[ex.arg_names[i]]
                           for i in self._p_arg_idx]

        # label positions within the non-donated arg pack, for the fold
        o_pos = {arg_i: pos for pos, arg_i in enumerate(self._o_arg_idx)}
        arg_pos = {n: i for i, n in enumerate(ex.arg_names)}
        self._label_o_pos = [o_pos[arg_pos[d.name]]
                             for d in self._group.label_shapes
                             if d.name in arg_pos]
        # data positions, for the device-feed mode: a CachedImageRecordIter
        # batch with ``batch.aug`` ships raw uint8 frames that ride these
        # slots of the non-donated pack; cast+crop+mirror+normalize run
        # inside the jit before the forward pass
        self._data_o_pos = [o_pos[arg_pos[d.name]]
                            for d in self._group.data_shapes
                            if d.name in arg_pos]
        self._fold_leaves = self._foldable_leaves(eval_metric)

        # the numerics plane (env-armed, or implicitly by a routable
        # Monitor): its stats pack rides this step's donated state
        from . import numwatch as _numwatch

        self._numwatch = _numwatch.maybe_plane(self)

        # optimizer states must exist before the first trace
        for upd_i, arg_i in zip(self._p_upd_idx, self._p_arg_idx):
            if upd_i not in self._updater.states:
                self._updater.states[upd_i] = \
                    self._optimizer.create_state(upd_i,
                                                 ex.arg_arrays[arg_i])

        self._jit_cache = {}
        self._seam_plan = None      # _update_seam's, once a bind
        self._seen_sigs = set()
        self._retrace_san = (_san.RetraceSanitizer()
                             if _san.enabled("retrace") else None)

    def _foldable_leaves(self, eval_metric):
        """The metric's leaves when EVERY one can fold on device (and a
        label exists per output); None -> metric updates host-side from
        the step's outputs (still one dispatch for fwd+bwd+update)."""
        from . import metric as _metric

        leaves = (list(eval_metric.metrics)
                  if isinstance(eval_metric, _metric.CompositeEvalMetric)
                  else [eval_metric])
        if not leaves or not self._label_o_pos:
            return None
        if len(self._label_o_pos) != len(self._executor.output_names):
            return None
        if not all(lf.has_device_fold and lf.num is None for lf in leaves):
            return None
        return leaves

    # ------------------------------------------------------------------
    # checkpoint support (checkpoint.py)
    @property
    def trace_cache_size(self) -> int:
        """Distinct trace signatures seen (== jit retraces). A resume
        that re-places restored state with the same avals/shardings as
        fresh init must NOT grow this — the elastic-rejoin tests assert
        the delta across a restore is zero."""
        return len(self._seen_sigs)

    def state_arrays(self):
        """The donated training-state NDArrays by role — the exact
        packs :mod:`mxnet_tpu.checkpoint` snapshots/restores, derived
        from the same index maps the dispatch uses so the two can never
        disagree about what "full state" means.

        Returns ``{"params": {name: NDArray}, "aux": {name: NDArray},
        "updater_slots": {upd_i: param_name}}``.
        """
        ex = self._executor
        params = {ex.arg_names[i]: ex.arg_arrays[i]
                  for i in self._p_arg_idx}
        aux = dict(zip(self._group.aux_names, ex.aux_arrays))
        slots = {upd_i: ex.arg_names[arg_i]
                 for upd_i, arg_i in zip(self._p_upd_idx,
                                         self._p_arg_idx)}
        return {"params": params, "aux": aux, "updater_slots": slots}

    # ------------------------------------------------------------------
    def jit_entries(self) -> int:
        """Compiled entries the step's jits hold: what the jits
        themselves count, so a step that compiled twice behind one shape
        signature (``step.fused_recompiles`` sees shapes only) shows."""
        return sum(fn._cache_size() for fn in self._jit_cache.values())

    def step(self, data_batch, eval_metric):
        """Run one training batch as one XLA dispatch."""
        with _tel.span("step.marshal"):
            do, const_vars, mutable_vars = self._marshal(data_batch)
        get_engine().push(do, const_vars=const_vars,
                          mutable_vars=mutable_vars, prop="fused_step")
        self._module._params_dirty = True
        _tel.inc("step.fused_steps")
        if self._fold_leaves is None:
            # unsupported metric: update host-side from the fused step's
            # outputs — still one dispatch for fwd+bwd+update
            eval_metric.update(data_batch.label, self._executor.outputs)

    def _marshal(self, data_batch):
        """Host work of one step that cannot trace: place the batch,
        the optimizer's per-step plans, the key, the argument packs.
        Returns the closure that dispatches it and the engine
        variables it reads and writes."""
        import jax.numpy as jnp

        ex = self._executor
        aug = getattr(data_batch, "aug", None)
        if aug is not None and len(self._data_o_pos) != 1:
            # in-graph augmentation is defined for the single image input
            # the cached iterators produce; anything else materializes
            from .io_cache import materialize_device_feed

            data_batch = materialize_device_feed(data_batch)
            aug = None
        if aug is None:
            self._group.load_data_batch(data_batch)
        else:
            # device feed: only the labels go through the normal loader;
            # the raw uint8 frames bypass the executor's (float, cropped)
            # data buffer and ride the non-donated pack directly
            self._group.load_label_batch(data_batch)

        opt = self._optimizer
        states = self._updater.states
        clip = opt.clip_gradient
        rescale = opt.rescale_grad
        # host-side per-step plans (update counts, lr schedule); grouped
        # by (kind, n_states) exactly like Optimizer.update_multi
        groups = {}
        for pos, upd_i in zip(range(len(self._p_arg_idx)),
                              self._p_upd_idx):
            w = ex.arg_arrays[self._p_arg_idx[pos]]
            kind, st, scalars = opt._plan(upd_i, w, w, states[upd_i])
            full = (rescale,) + tuple(scalars) \
                + ((clip,) if clip is not None else ())
            groups.setdefault((kind, len(st)), []).append(
                (pos, tuple(st), full))
        specs = []
        state_nds = []
        sv_mats = []
        # sanctioned H2D: the host-side update plans become one small
        # device mat per param group (graftlint: jnp.asarray of a host
        # list; transfer sanitizer: explicit allow window)
        mesh = getattr(self._group, "_mesh", None)
        with _san.intentional_transfer():
            rep = None
            if mesh is not None:
                # pre-place replicated on the mesh: leaving the mats on
                # device 0 would make every dispatch an implicit d2d
                import jax
                from jax.sharding import NamedSharding, PartitionSpec
                rep = NamedSharding(mesh, PartitionSpec())
            for (kind, n_states), members in groups.items():
                specs.append((kind, n_states,
                              tuple(m[0] for m in members)))
                state_nds.append(tuple(m[1] for m in members))
                mat = jnp.asarray([m[2] for m in members], jnp.float32)
                if rep is not None:
                    mat = jax.device_put(mat, rep)
                sv_mats.append(mat)
        specs = tuple(specs)

        from .optimizer import _donation_ok

        donate = _donation_ok()
        fold = self._fold_leaves is not None
        feed = None
        if aug is not None:
            # static augmentation config; the per-batch offsets/flags and
            # mean/scale are traced arguments, so a new batch (or an lr-
            # style mean/scale change) never recompiles
            d0 = self._group.data_shapes[0].shape
            nchw = aug["layout"] == "NCHW"
            if nchw:
                c, h, w = d0[1], d0[2], d0[3]
            else:
                h, w, c = d0[1], d0[2], d0[3]
            feed = (nchw, h, w, c)
        nw = self._numwatch
        ck = (specs, clip is not None, donate, fold, feed,
              None if nw is None else nw.trace_key)
        fn = self._jit_cache.get(ck)
        if fn is None:
            with _tel.span("step.build"):
                fn = self._build(specs, clip is not None, donate, fold,
                                 feed, watch=nw)
            self._jit_cache[ck] = fn

        with _san.intentional_transfer():
            # fold_in of the host step counter: the one int H2D per step
            key = ex._key()
        ex._last_key = key
        p_nds = [ex.arg_arrays[i] for i in self._p_arg_idx]
        o_nds = [ex.arg_arrays[i] for i in self._o_arg_idx]
        p_vals = [nd._data for nd in p_nds]
        o_vals = [nd._data for nd in o_nds]
        aug_vals = None
        if aug is not None:
            grp = self._group
            # uint8 frames, batch-sharded like any data arg (the H2D
            # moved 1/4 the float bytes; nd.array counted it already)
            o_vals[self._data_o_pos[0]] = \
                grp._place(data_batch.data[0], 0)._data
            import numpy as _np

            aug_vals = (
                grp._place(_np.asarray(aug["tops"],  # graft: host-sync
                                       _np.int32), 0)._data,
                grp._place(_np.asarray(aug["lefts"],  # graft: host-sync
                                       _np.int32), 0)._data,
                grp._place(_np.asarray(aug["mirror"],  # graft: host-sync
                                       bool), 0)._data,
                grp._place(_np.asarray(aug["mean"],  # graft: host-sync
                                       _np.float32), None)._data,
                grp._place(_np.asarray(aug["scale"],  # graft: host-sync
                                       _np.float32), None)._data,
            )
            _tel.inc("step.fused_feed_batches")
        aux_vals = [a._data for a in ex.aux_arrays]
        st_vals = tuple(
            tuple(tuple(s._data for s in member) for member in grp)
            for grp in state_nds)
        leaves = self._fold_leaves if fold else ()
        accs = []
        for leaf in leaves:
            acc = leaf._device_acc
            if acc is None:
                # placed to match the (possibly mesh-sharded) params so
                # the jit sees one consistent device set; two distinct
                # buffers because the acc pack is donated
                from .metric import _replicated_zero

                like = p_vals[0] if p_vals else None
                with _san.intentional_transfer():
                    acc = (_replicated_zero(like),
                           _replicated_zero(like))
            accs.append(tuple(acc))
        accs = tuple(accs)
        stats = None
        if nw is not None:
            # the numerics stats pack is donated like the accs: placed
            # once (replicated on the params' mesh), swapped in-place by
            # every dispatch's write-back
            with _san.intentional_transfer():
                stats = nw.device_pack(p_vals[0] if p_vals else None)

        # a fresh (shape, dtype, spec) signature means jax retraces and
        # XLA recompiles — in steady state that's the silent stall the
        # RecompileDetector turns into an anomaly event
        sig = ck + (tuple((v.shape, str(v.dtype))
                          for v in p_vals + o_vals + aux_vals),)
        new_entry = sig not in self._seen_sigs
        if new_entry:
            self._seen_sigs.add(sig)
            _tel.inc("step.fused_recompiles")
        if self._retrace_san is not None:
            self._retrace_san.check(len(self._seen_sigs))

        mut = [nd._var for nd in p_nds] \
            + [a._var for a in ex.aux_arrays] \
            + [s._var for grp in state_nds for member in grp
               for s in member]

        def _do():
            _tel.inc("step.dispatches")
            if nw is not None:
                args = (p_vals, o_vals, aux_vals, st_vals, sv_mats,
                        accs, stats, key)
            else:
                args = (p_vals, o_vals, aux_vals, st_vals, sv_mats,
                        accs, key)
            if aug_vals is not None:
                args = args + (aug_vals,)
            if new_entry:
                # the first call of a new jit entry traces, lowers and
                # compiles (or reads the cache) before it dispatches
                with _tel.span("step.build"):
                    with _tel.span("step.dispatch"):
                        res = fn(*args)
            else:
                with _tel.span("step.dispatch"):
                    res = fn(*args)
            if _tel.enabled():
                # after every dispatch, not only a build the signature
                # announced: the entry that matters is the one it missed
                _tel.set_gauge("step.fused_jit_entries",
                               self.jit_entries())
            with _tel.span("step.write_back"):
                if nw is not None:
                    new_p, outs, aux_out, new_st, new_accs, new_stats = res
                    nw.write_back(new_stats)
                else:
                    new_p, outs, aux_out, new_st, new_accs = res
                for nd, v in zip(p_nds, new_p):
                    nd._data = v
                for nd, v in zip(ex.aux_arrays, aux_out):
                    nd._data = v
                for grp, new_grp in zip(state_nds, new_st):
                    for member, new_member in zip(grp, new_grp):
                        for snd, sv in zip(member, new_member):
                            snd._data = sv
                for leaf, acc in zip(leaves, new_accs):
                    leaf._device_acc = acc
                ex._set_outputs(outs)
                ex._train_pending = False
            if donate and _san.enabled("donation"):
                # argnums (0, 2, 3, 5[, 6]): params, aux, opt states,
                # accs, and the numwatch stats pack when armed
                _san.DonationSanitizer.check(
                    "the fused step",
                    p_vals + aux_vals
                    + [s for g in st_vals for m in g for s in m]
                    + [a for acc in accs for a in acc]
                    + ([stats] if stats is not None else []))
            return list(new_p)

        return _do, [nd._var for nd in o_nds], mut

    # ------------------------------------------------------------------
    def _update_seam(self, specs):
        """The parameters' positions whose update follows its product
        (:func:`_plan_update_seam`), from the bound shapes, reckoned once
        a bind: the products' operations as the graph's nodes state them,
        a device's share of them under a mesh, against the bytes that
        device's shard of the weight and of each optimizer state is read
        and written with."""
        if self._seam_plan is not None:
            return self._seam_plan
        ex = self._executor
        mesh = getattr(self._group, "_mesh", None)
        device = (ex._ctx.jax_device() if mesh is None
                  else mesh.devices.flat[0])
        try:
            peaks = _xprof.chip_peaks(device.device_kind)
        except MXNetError:
            peaks = None        # no published peak: as where there is none
        stated = _weight_grad_flops(
            ex._symbol, {n: a.shape for n, a in zip(ex.arg_names,
                                                    ex.arg_arrays)})
        n_states = {pos: n for _, n, positions in specs
                    for pos in positions}
        flops, update_bytes = [], []
        for pos, i in enumerate(self._p_arg_idx):
            w = ex.arg_arrays[i]._data
            f = stated.get(ex.arg_names[i])
            flops.append(None if f is None
                         else f / (1 if mesh is None else mesh.size))
            update_bytes.append(
                math.prod(w.sharding.shard_shape(w.shape))
                * w.dtype.itemsize * (1 + n_states[pos]) * 2)
        self._seam_plan = _plan_update_seam(flops, update_bytes, peaks)
        return self._seam_plan

    def _build(self, specs, clipped, donate, fold, feed=None, watch=None):
        """Trace+compile the whole-batch step for one (structure,
        donation, fold, feed) configuration. With ``feed`` set the data
        slot of the non-donated pack holds raw uint8 stored frames and
        ``aug`` carries (tops, lefts, mirror, mean, scale): cast + crop +
        mirror + normalize + layout run in-graph, the same math (and so
        the same bits) as CachedImageRecordIter._device_augment, fused
        into the one donated dispatch."""
        import jax
        import jax.numpy as jnp

        from .optimizer import _update_math

        ex = self._executor
        run_graph = ex._run_graph
        n_args = len(ex.arg_names)
        # in-jit gradient exchange: with the batch sharded over the
        # mesh's data axes, pinning each vjp gradient to its PARAM's
        # sharding makes GSPMD lower the exchange INSIDE this dispatch
        # (rescale_grad is 1/global_batch, so the sum over shards is the
        # mean). A replicated param gets a mean-psum all-reduce; an
        # fsdp-sharded param gets the ZeRO reduce-scatter (each device
        # keeps only its shard of the reduced grad, then updates only
        # its shard of the param/opt-state). Without the constraint the
        # partitioner may defer the reduce into the update — correct but
        # unpinned; with it the collective is a guaranteed,
        # xprof-visible op between backward and update. The kvstore's
        # reduce spec (DeviceSyncKVStore.grad_reduce_sharding) owns the
        # mapping so future recipes can widen it without touching this.
        grad_shardings = None
        mesh = getattr(self._group, "_mesh", None)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(mesh, PartitionSpec())
            kv = getattr(self._module, "_kvstore", None)
            reduce_spec = getattr(kv, "grad_reduce_sharding", None)
            grad_shardings = []
            param_shardings = []
            for i in self._p_arg_idx:
                ps = self._group.param_sharding(ex.arg_names[i]) or rep
                param_shardings.append(ps)
                if reduce_spec is not None:
                    ps = reduce_spec(mesh, ps) or ps
                grad_shardings.append(ps)
        p_idx = list(self._p_arg_idx)
        o_idx = list(self._o_arg_idx)
        label_pos = list(self._label_o_pos)
        data_pos = self._data_o_pos[0] if self._data_o_pos else None
        leaves = self._fold_leaves or ()
        math_fns = {(kind, n): _update_math(kind, n, clipped)
                    for kind, n, _ in specs}
        apart = self._update_seam(specs)
        near = _outputs_nearest_parameters(
            ex._symbol, {ex.arg_names[i] for i in p_idx})
        tied = [pos for pos, i in enumerate(p_idx)
                if ex.arg_names[i] in near]

        _tel.inc("executor.jit_build")

        def _augment(x, aug):
            nchw, h, w, c = feed
            tops, lefts, mirror, mean, scale = aug

            def one(img, t, l, mi):
                crop = jax.lax.dynamic_slice(img, (t, l, 0), (h, w, c))
                return jnp.where(mi, crop[:, ::-1], crop)

            y = jax.vmap(one)(x, tops, lefts, mirror)
            y = (y.astype(jnp.float32) - mean) * scale
            return jnp.transpose(y, (0, 3, 1, 2)) if nchw else y

        def _core(p_vals, o_vals, aux, st, sv_mats, accs, stats, key,
                  aug=None):
            full = [None] * n_args
            for pos, i in enumerate(o_idx):
                full[i] = o_vals[pos]
            if feed is not None:
                full[o_idx[data_pos]] = _augment(o_vals[data_pos], aug)

            def f(pv):
                fl = list(full)
                for pos, i in enumerate(p_idx):
                    fl[i] = pv[pos]
                return run_graph(fl, aux, key, True)

            # the scopes are names on the compiled ops (metadata only):
            # a device trace tells the four phases apart by them
            with jax.named_scope("fwd"):
                res, vjp = jax.vjp(f, list(p_vals))
            outs, aux_out = res
            with jax.named_scope("bwd"):
                heads = [jnp.ones_like(o)
                         if jnp.issubdtype(o.dtype, jnp.inexact)
                         else zero_cotangent(o) for o in outs]
                cts = (heads,
                       jax.tree_util.tree_map(zero_cotangent, aux_out))
                grads, = vjp(cts)
                if grad_shardings is not None:
                    grads = [jax.lax.with_sharding_constraint(g, s)
                             for g, s in zip(grads, grad_shardings)]
                # the seam: a gradient that goes apart is written once,
                # as the float32 the update would have read anyway (what
                # the collective summed, under a mesh), and the compiler
                # may not fuse through. One barrier a gradient: one over
                # all of them would hold every gradient at once
                grads = [jax.lax.optimization_barrier(g) if pos in apart
                         else g for pos, g in enumerate(grads)]
                _tel.inc("step.update_seam.apart", len(apart))
                _tel.inc("step.update_seam.riding",
                         len(grads) - len(apart))
                _tel.set_gauge("step.update_seam.apart_bytes", sum(
                    grads[pos].size * grads[pos].dtype.itemsize
                    for pos in apart))
            new_p = list(p_vals)
            new_st = []
            if tied and sum(o.size * o.dtype.itemsize
                            for o in outs) >= _OUTPUTS_FLOOR_BYTES:
                # the outputs are complete before what they were computed
                # from is overwritten (the comment at _OUTPUTS_FLOOR_BYTES)
                held, outs = jax.lax.optimization_barrier(
                    ([new_p[pos] for pos in tied], outs))
                for pos, value in zip(tied, held):
                    new_p[pos] = value
                _tel.inc("step.outputs_before_update", len(tied))
            with jax.named_scope("update"):
                for gi, (kind, n_states, positions) in enumerate(specs):
                    math_fn = math_fns[(kind, n_states)]
                    grp = []
                    for j, pos in enumerate(positions):
                        nw, ns = math_fn(new_p[pos], grads[pos],
                                         st[gi][j], sv_mats[gi][j])
                        new_p[pos] = nw
                        grp.append(ns)
                    new_st.append(tuple(grp))
            if grad_shardings is not None:
                # every piece of carried state leaves the step on the
                # sharding it entered with. Updated params stay on their
                # (fsdp) shardings so GSPMD never gathers them just to
                # re-scatter on entry to the next step; and an output
                # GSPMD is left free to place (BN stats, optimizer
                # state) can come back sharded differently, which
                # changes the NEXT call's input shardings and makes jit
                # compile the whole step a second time — silently, since
                # step.fused_recompiles only sees shapes
                pin = jax.lax.with_sharding_constraint
                new_p = [pin(p, s) for p, s in zip(new_p, param_shardings)]
                aux_out = [pin(a, rep) for a in aux_out]
                for gi, (_, _, positions) in enumerate(specs):
                    # same rule as place_like_param: a state leaf shaped
                    # like its weight shares the weight's sharding
                    new_st[gi] = tuple(
                        jax.tree_util.tree_map(
                            lambda leaf, pos=pos: pin(
                                leaf, param_shardings[pos]
                                if leaf.shape == new_p[pos].shape
                                else rep), ns)
                        for pos, ns in zip(positions, new_st[gi]))
            new_accs = accs
            labels = [o_vals[p] for p in label_pos]
            if fold:
                new_accs = []
                with jax.named_scope("metric"):
                    for leaf, (s, c) in zip(leaves, accs):
                        for lab, pred in zip(labels, outs):
                            ds, dc = leaf.device_fold(lab, pred)
                            s = s + ds
                            c = c + dc
                        new_accs.append((s, c))
                new_accs = tuple(new_accs)
            new_p = tuple(new_p)
            new_st = tuple(new_st)
            if watch is None:
                return (new_p, outs, aux_out, new_st, new_accs)
            # numerics stats fold — same trace, same dispatch
            new_stats, grads_ok = watch.fold(stats, p_vals, grads,
                                             new_p, outs, labels)
            if watch.skip_guard:
                # nonfinite grads: select the step k-1 training state
                # in-graph (params/opt-state/metric accs bit-identical
                # to the pre-step buffers) — still one dispatch; the
                # pack itself always advances so the host sees the skip
                keep = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(grads_ok, new, old),
                    (new_p, new_st, new_accs),
                    (tuple(p_vals), tuple(st), tuple(accs)))
                new_p, new_st, new_accs = keep
            return (new_p, outs, aux_out, new_st, new_accs, new_stats)

        # route the compile through the device observability plane: a
        # plain jax.jit when xprof is off, else the AOT wrapper that
        # times the compile, records FLOPs/memory/op breakdown and the
        # retrace-cause diff — still the same one donated dispatch.
        # leaf names come from the executor, so a retrace diff says
        # "batch.data" / "params.fc1_weight" instead of "arg1[0]"
        names = [ex.arg_names[i] for i in self._p_arg_idx]
        batch_names = [ex.arg_names[i] for i in self._o_arg_idx]
        if watch is not None:
            # the stats pack joins the donated set (argnum 6)
            def step(p_vals, o_vals, aux, st, sv_mats, accs, stats, key,
                     aug=None):
                return _core(p_vals, o_vals, aux, st, sv_mats, accs,
                             stats, key, aug)

            arg_names = (tuple("params." + n for n in names),
                         tuple("batch." + n for n in batch_names),
                         "aux", "opt_state", "hyper", "metric_acc",
                         "numwatch_pack", "rng_key", "aug")
            donate_argnums = (0, 2, 3, 5, 6)
        else:
            def step(p_vals, o_vals, aux, st, sv_mats, accs, key,
                     aug=None):
                return _core(p_vals, o_vals, aux, st, sv_mats, accs,
                             None, key, aug)

            arg_names = (tuple("params." + n for n in names),
                         tuple("batch." + n for n in batch_names),
                         "aux", "opt_state", "hyper", "metric_acc",
                         "rng_key", "aug")
            donate_argnums = (0, 2, 3, 5)
        # the one site that traces under the phases' scopes: its record
        # carries the census of the program's instructions by phase
        return _xprof.jit(
            step, site="fused_step", arg_names=arg_names, census=True,
            donate_argnums=donate_argnums if donate else ())


# ---------------------------------------------------------------------------
# fused inference
# ---------------------------------------------------------------------------

def make_fused_infer(executor, data_names, top_k=0, mesh=None):
    """Build a :class:`FusedInfer` over a bound executor: forward plus
    on-device argmax/top-k post-processing compiled into ONE dispatch
    per batch, with the non-data args (params + BN stats) packed and
    device-placed once. Unlike the train step nothing is donated — the
    same executable serves every subsequent batch of the same shape.

    ``data_names`` are the per-request argument slots; every other arg
    is part of the params pack. ``top_k=0`` skips post-processing,
    ``top_k=1`` appends an argmax over the last axis of the first
    output, ``top_k>1`` appends ``jax.lax.top_k`` values+indices.
    ``mesh`` shards the batch axis of incoming data across its data
    axes (``dp``); on a ``(dp, tp)`` mesh the params pack additionally
    NamedSharding-shards along ``tp`` (per-param dim via
    :func:`~mxnet_tpu.parallel.sharding.tp_param_spec`) so a model
    bigger than one chip's HBM serves from the shards, with the
    activation resharding collectives emitted by GSPMD INSIDE the one
    dispatch. Off a tp mesh the pack replicates as before."""
    return FusedInfer(executor, data_names, top_k=top_k, mesh=mesh)


class FusedInfer:
    """Compiled-once single-dispatch inference step.

    Host work per batch is only the H2D of the request data (sanctioned
    transfer window; skipped entirely when the caller hands over
    already-placed jax arrays) and the executable lookup. Params are
    packed at construction (refresh with :meth:`refresh_params` after a
    weight update); the rng key is fixed — ``is_train=False`` disables
    dropout, so it never feeds randomness.

    Telemetry: ``infer.dispatches`` counts XLA launches (exactly one
    per call), ``infer.recompiles`` counts fresh data-shape signatures
    — under the serving bucket ladder this saturates at
    ``len(buckets)`` and stays flat in steady state (the xprof
    ``fused_infer`` site proves it at the compile registry).
    """

    #: Retry-safety contract: a dispatch donates nothing and mutates no
    #: state, so serving a duplicate (hedged/retried) request twice is
    #: harmless — the scheduler's request-id dedup keys off this tag.
    idempotent = True

    def __init__(self, executor, data_names, top_k=0, mesh=None):
        from .base import MXNetError

        self._ex = ex = executor
        arg_pos = {n: i for i, n in enumerate(ex.arg_names)}
        missing = [n for n in data_names if n not in arg_pos]
        if missing:
            raise MXNetError("fused_infer data args %s not in the "
                             "executor's arguments" % (missing,))
        self._data_names = list(data_names)
        self._d_idx = [arg_pos[n] for n in data_names]
        d_set = set(self._d_idx)
        self._p_idx = [i for i in range(len(ex.arg_names))
                       if i not in d_set]
        self._top_k = int(top_k)
        self._mesh = mesh
        # off-mesh placement target: the executor's OWN device, not the
        # process default — on a TPU host a module bound to mx.cpu()
        # must not have its request rows land on the chip
        self._device = ex._ctx.jax_device()
        self._tp = 1
        if mesh is not None and "tp" in mesh.axis_names:
            self._tp = int(mesh.shape["tp"])
        self._fn = self._build()
        self._seen_sigs = set()
        self._param_vals = None
        self._aux_vals = None
        # per-param content digests (sha256 over host bytes, the same
        # hashing checkpoint.snapshot records in its manifest): the
        # resident-pack side of the delta-aware refresh. None = unknown
        # provenance, so the next streamed refresh transfers everything
        # and re-seeds.
        self._digests = None
        self.last_refresh_bytes = 0
        self.last_refresh_ms = 0.0
        self.last_refresh_changed = 0
        self.last_refresh_skipped = 0
        with _san.intentional_transfer():
            # one fixed key for every dispatch: is_train=False, so the
            # graph's rng is inert — a per-call fold_in would be one
            # host int H2D per request batch for nothing
            self._key = ex._key()
        self.refresh_params()

    # ------------------------------------------------------------------
    @property
    def compiles(self) -> int:
        """Distinct data-shape signatures seen (== jit retraces)."""
        return len(self._seen_sigs)

    @property
    def mesh_key(self):
        """Mesh-factoring fingerprint this executable was built for
        (``(("dp", 4), ("tp", 2))``-style tuple, None off-mesh) — the
        cache key a re-bind across meshes must miss on."""
        if self._mesh is None:
            return None
        from .parallel.sharding import mesh_axis_sizes

        return tuple(mesh_axis_sizes(self._mesh).items())

    @staticmethod
    def factoring_key(mesh):
        """The :attr:`mesh_key` a FusedInfer built over ``mesh`` would
        carry — for callers checking a cached instance without one."""
        if mesh is None:
            return None
        from .parallel.sharding import mesh_axis_sizes

        return tuple(mesh_axis_sizes(mesh).items())

    def stale_for(self, executor, mesh=None) -> bool:
        """True when this cached executable no longer matches the
        caller's executor or mesh factoring: dispatching it would reuse
        an AOT executable compiled for the OLD placement. Rebuild
        instead (predictor.py and InferenceServer both key off this)."""
        return (executor is not self._ex
                or self.factoring_key(mesh) != self.mesh_key)

    def _replicated(self):
        if self._mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self._mesh, PartitionSpec())

    def _param_sharding(self, arg_i):
        """NamedSharding for one params-pack member: tp-sharded on the
        per-param dim :func:`tp_param_spec` picks when the mesh carries
        a ``tp`` axis (replicated when no dim divides), replicated on a
        data-only mesh, None off-mesh."""
        if self._mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        if self._tp > 1:
            from .parallel.sharding import tp_param_spec

            shape = tuple(self._ex.arg_arrays[arg_i]._data.shape)
            spec = tp_param_spec(shape, self._mesh) or PartitionSpec()
            return NamedSharding(self._mesh, spec)
        return NamedSharding(self._mesh, PartitionSpec())

    def _batch_sharding(self, ndim):
        """Request batches shard over the mesh's DATA axes only —
        ``dp`` (and ``fsdp`` when a training mesh is reused), never
        ``tp``: the model axis splits params, not rows."""
        if self._mesh is None:
            return None
        from jax.sharding import NamedSharding

        from .parallel.sharding import batch_spec

        return NamedSharding(self._mesh, batch_spec(self._mesh, 0))

    def refresh_params(self, host_params=None, digests=None,
                       torn_ms: float = 0.0):
        """(Re)pack the non-data args + aux states, placed per
        :meth:`_param_sharding` (tp-sharded on a ``(dp, tp)`` mesh,
        replicated otherwise).

        Two entry modes:

        * **full re-pack** (no arguments) — after ``module.set_params``
          the whole pack re-places from the executor's arrays, exactly
          the pre-delta behaviour. Resident digests reset to unknown.
        * **delta stream** (``host_params``: name -> host ndarray) —
          the checkpoint-streamed path. Each incoming param's sha256
          (``digests[name]`` when the caller already has it from the
          snapshot manifest, hashed here otherwise) is diffed against
          the resident pack's digest and ONLY changed params transfer
          and re-place inside the ``intentional_transfer`` window; the
          executor's arrays are written through so a later full re-pack
          agrees. ``MXNET_TPU_REFRESH_DELTA=0`` transfers everything
          regardless (the diff bypass hatch).

        Telemetry either way: ``infer.refresh_bytes`` (host bytes
        moved), ``infer.refresh_ms``, ``infer.refresh_changed`` /
        ``infer.refresh_skipped`` param counts — mirrored on
        ``last_refresh_*`` attributes.

        ``torn_ms > 0`` (the ``torn_swap`` injected fault) makes the
        swap deliberately non-atomic: half the new pack lands, then a
        sleep of ``torn_ms``, then the rest — a dispatch inside that
        window reads mixed param versions. Serving callers must drain
        the replica first; the fleet's rolling swap does."""
        import time as _time

        import jax

        ex = self._ex
        t0 = _time.perf_counter()
        moved = 0
        changed = skipped = 0
        if host_params is not None:
            from .checkpoint import param_digest

            delta_on = (_env.get("MXNET_TPU_REFRESH_DELTA")
                        and self._digests is not None)
            new_params = list(self._param_vals)
            new_aux = self._aux_vals
            new_digests = dict(self._digests or {})
            pos_of = {ex.arg_names[i]: pos
                      for pos, i in enumerate(self._p_idx)}
            with _san.intentional_transfer():
                for name, host in host_params.items():
                    pos = pos_of.get(name)
                    if pos is None:
                        continue   # a data arg, not part of the pack
                    dg = ((digests or {}).get(name)
                          or param_digest(host))
                    if delta_on and new_digests.get(name) == dg:
                        skipped += 1
                        continue
                    arg_i = self._p_idx[pos]
                    sh = self._param_sharding(arg_i)
                    val = jax.device_put(
                        host, sh if sh is not None else self._device)
                    new_params[pos] = val
                    # write-through so a later full re-pack (or a
                    # host-side get_params) sees the streamed values
                    ex.arg_arrays[arg_i]._data = val
                    new_digests[name] = dg
                    changed += 1
                    moved += int(getattr(host, "nbytes", 0))
        else:
            with _san.intentional_transfer():
                new_params = []
                for i in self._p_idx:
                    sh = self._param_sharding(i)
                    v = ex.arg_arrays[i]._data
                    new_params.append(jax.device_put(v, sh)
                                      if sh is not None else v)
                rep = self._replicated()
                new_aux = [jax.device_put(a._data, rep)
                           if rep is not None else a._data
                           for a in ex.aux_arrays]
            changed = len(new_params)
            moved = sum(int(v.nbytes) for v in new_params)
            new_digests = None   # unknown provenance: next delta
            #                      refresh transfers all and re-seeds
        self.last_refresh_bytes = moved
        self.last_refresh_changed = changed
        self.last_refresh_skipped = skipped
        _tel.inc("infer.refresh_bytes", moved)
        _tel.inc("infer.refresh_changed", changed)
        _tel.inc("infer.refresh_skipped", skipped)
        if torn_ms > 0 and self._param_vals is not None and new_params:
            half = max(1, len(new_params) // 2)
            self._param_vals = (new_params[:half]
                                + self._param_vals[half:])
            _time.sleep(torn_ms / 1e3)
            self._param_vals = new_params
            self._aux_vals = new_aux
            self._digests = new_digests
            self.last_refresh_ms = (_time.perf_counter() - t0) * 1e3
            _tel.observe("infer.refresh_ms", self.last_refresh_ms)
            return
        self._param_vals = new_params
        self._aux_vals = new_aux
        self._digests = new_digests
        self.last_refresh_ms = (_time.perf_counter() - t0) * 1e3
        _tel.observe("infer.refresh_ms", self.last_refresh_ms)

    def place_batch(self, arrays):
        """Device-place one request batch (numpy or jax arrays), batch
        axis sharded along ``dp`` under a mesh. Already-placed jax
        arrays pass through untouched off-mesh."""
        import jax
        import numpy as _np

        placed = []
        with _san.intentional_transfer():
            for a in arrays:
                sh = self._batch_sharding(getattr(a, "ndim", 0) or 1)
                if sh is not None:
                    placed.append(jax.device_put(a, sh))
                elif isinstance(a, _np.ndarray):
                    placed.append(jax.device_put(a, self._device))
                else:
                    placed.append(a)
        return placed

    # ------------------------------------------------------------------
    def __call__(self, arrays):
        """One batch -> (outputs, post) in ONE dispatch. ``arrays``
        follow ``data_names`` order and must already be padded to a
        stable shape (the serving bucket ladder / the bound batch
        size); ``post`` is ``()`` for top_k=0, ``(argmax,)`` for
        top_k=1, ``(values, indices)`` otherwise. Results stay on
        device — the caller decides what (and when) to fetch."""
        d_vals = self.place_batch(arrays)
        sig = tuple((tuple(v.shape), str(v.dtype)) for v in d_vals)
        if sig not in self._seen_sigs:
            self._seen_sigs.add(sig)
            _tel.inc("infer.recompiles")
        _tel.inc("infer.dispatches")
        return self._fn(self._param_vals, d_vals, self._aux_vals,
                        self._key)

    # ------------------------------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp

        ex = self._ex
        run_graph = ex._run_graph
        n_args = len(ex.arg_names)
        p_idx = list(self._p_idx)
        d_idx = list(self._d_idx)
        top_k = self._top_k
        # tensor-sharded serving: pin every forward output back to the
        # batch (data-axes) sharding. With params split along ``tp``
        # the activations come out of the matmuls partially-summed or
        # model-sharded; the constraint makes GSPMD emit the
        # all-reduce/all-gather INSIDE this one dispatch (the xprof
        # collective bucket is the proof) instead of deferring a
        # gather to the host fetch. Off a tp mesh the outputs are
        # already batch-sharded and no constraint is needed.
        batch_out = None
        if self._mesh is not None and self._tp > 1:
            from jax.sharding import NamedSharding

            from .parallel.sharding import batch_spec

            batch_out = NamedSharding(self._mesh,
                                      batch_spec(self._mesh, 0))

        _tel.inc("executor.jit_build")

        def infer(p_vals, d_vals, aux, key):
            full = [None] * n_args
            for pos, i in enumerate(p_idx):
                full[i] = p_vals[pos]
            for pos, i in enumerate(d_idx):
                full[i] = d_vals[pos]
            outs, _ = run_graph(full, aux, key, False)
            if batch_out is not None:
                outs = [jax.lax.with_sharding_constraint(o, batch_out)
                        if getattr(o, "ndim", 0) >= 1 else o
                        for o in outs]
            post = ()
            if top_k and outs:
                head = outs[0]
                if (head.ndim >= 2
                        and jnp.issubdtype(head.dtype, jnp.inexact)):
                    if top_k == 1:
                        post = (jnp.argmax(head, axis=-1),)
                    else:
                        post = tuple(jax.lax.top_k(head, top_k))
            return tuple(outs), post

        names = [ex.arg_names[i] for i in p_idx]
        return _xprof.jit(
            infer, site="fused_infer",
            arg_names=(tuple("params." + n for n in names),
                       tuple("batch." + n for n in self._data_names),
                       "aux", "rng_key"),
            donate_argnums=())
