"""In-graph numerics observability: model-health telemetry, NaN
provenance, and guarded training.

The stack observes every *system* dimension — step traces, device and
compile truth, request spans, fleet federation — but was blind to the
*model*: nothing watched gradient norms, nonfinite values, update-to-
weight ratios, or loss spikes, and the classic executor-callback
``Monitor`` forced the fused step to abandon its one-dispatch contract
entirely. This plane computes every statistic INSIDE the donated fused
jit, so ``dispatches_per_step`` stays exactly 1.0:

* a small f32 **stats pack** (one row per grad-bearing param, in
  forward order, plus one model-level META row) rides the donated state
  like the metric accumulators do — per-tensor gradient l2/max-abs/
  nonfinite-count/zero-count, param l2 and nonfinite count, and the
  update l2 that yields the update-to-weight ratio;
* the pack is host-fetched only every ``MXNET_TPU_NUMWATCH_EVERY_N``
  steps, one small D2H inside an ``intentional_transfer`` window — no
  extra dispatch, no per-step sync;
* **NaN/Inf provenance**: sticky ``first_bad_*`` columns stamp the step
  at which each tensor's params or grads first went nonfinite, so a
  fetch names the first layer to go bad (earliest step wins; a bad
  PARAM beats a bad GRAD at the same step, because one backward pass
  fans a single NaN out to every gradient; remaining ties break in
  forward order) — without a second dispatch;
* **guarded training** (``MXNET_TPU_NUMWATCH_GUARD``, off by default):
  ``skip`` selects the step k-1 params/opt-state/metric accs in-graph
  whenever any gradient is nonfinite (still one dispatch, params stay
  bit-identical to the pre-step state), ``rollback`` restores the last
  healthy CheckpointManager snapshot when a fetch sees nonfinite
  params. Both are counted and rate-limited;
* fetched health feeds ``numwatch.*`` telemetry, the step-record extras
  the tracing anomaly detectors read (loss-spike / grad-explosion /
  dead-update, see ``tracing.default_detectors``), a bounded health
  ring the FlightRecorder dumps on crash, and the rewritten
  :class:`~mxnet_tpu.monitor.Monitor` facade — so installing a default
  monitor no longer falls back to the three-dispatch loop.

Arming: ``MXNET_TPU_NUMWATCH=1``, or implicitly when a pack-expressible
``Monitor`` is installed on the executor.
"""
from __future__ import annotations

import logging
import math
from collections import deque
from typing import List, Optional

import numpy as np

from . import env as _env
from . import telemetry as _tel
from .analysis import sanitizers as _san

_log = logging.getLogger("mxnet_tpu.numwatch")

__all__ = ["NumWatch", "NumericsError", "maybe_plane", "monitor_routable",
           "after_step", "health_rows", "COLS", "META"]

# -- stats-pack layout ------------------------------------------------------
# One f32 matrix of shape (n_params + 1, NCOLS), donated alongside the
# metric accumulators. Rows 0..n-1 are the grad-bearing params in
# FORWARD order (the executor's _grad_idx order); the final row is the
# model-level META row. first_bad_* hold the 1-based in-graph step
# number at which the tensor first went nonfinite (0 = never) — an f32
# step counter is exact up to 2^24 steps.
COLS = ("g_sumsq", "g_maxabs", "g_nonfinite", "g_zero",
        "w_sumsq", "w_nonfinite", "upd_sumsq",
        "first_bad_param", "first_bad_grad")
(G_SUMSQ, G_MAXABS, G_NONFIN, G_ZERO,
 W_SUMSQ, W_NONFIN, UPD_SUMSQ, FB_PARAM, FB_GRAD) = range(len(COLS))
NCOLS = len(COLS)
# META row slots (rest of the row is zero padding)
META = ("step", "loss", "out_nonfinite", "skips")
(M_STEP, M_LOSS, M_OUT_NONFIN, M_SKIPS) = range(len(META))

# last-K fetched health rows, process-wide: the FlightRecorder writes
# these into every crash dump (numwatch.jsonl) so a post-mortem shows
# the model's numeric trajectory into the failure
_HEALTH_RING: deque = deque(maxlen=64)


class NumericsError(RuntimeError):
    """The guarded-training plane refused to continue: the model went
    nonfinite again inside the rollback cooldown (restoring the same
    snapshot in a loop would thrash, not recover)."""


def health_rows() -> List[dict]:
    """The last-K fetched health rows (crash-dump feed)."""
    return list(_HEALTH_RING)


def monitor_routable(mon) -> bool:
    """True when an installed ``Monitor``'s statistics are expressible
    from the stats pack — the default ``norm(x)/sqrt(x.size)`` stat over
    params and grads. Such monitors ride the fused step; only truly
    custom ``stat_func`` callables force the classic fallback."""
    return bool(getattr(mon, "pack_expressible", False))


def maybe_plane(fused) -> Optional["NumWatch"]:
    """Build the plane for a FusedTrainStep when armed — by env
    (``MXNET_TPU_NUMWATCH=1``) or implicitly by a pack-expressible
    installed Monitor — else None (and the step carries no pack)."""
    ex = fused._executor
    cb = ex._monitor_callback
    mon = getattr(cb, "__self__", None) if cb is not None else None
    if mon is not None and not monitor_routable(mon):
        mon = None
    if not _env.get("MXNET_TPU_NUMWATCH") and mon is None:
        return None
    names = [ex.arg_names[i] for i in fused._p_arg_idx]
    sizes = [int(np.prod(ex.arg_dict[n].shape)) or 1 for n in names]
    plane = NumWatch(names, sizes, monitor=mon)
    if mon is not None:
        mon.attach_plane(plane)
    return plane


def after_step(plane: Optional["NumWatch"]):
    """The fit loop's per-batch entry point. The disabled path
    (``plane=None``) must cost one None check and nothing else — it is
    pinned below 2 µs by test_numwatch."""
    if plane is None:
        return None
    return plane.after_step()


class NumWatch:
    """The numerics plane bound to one fused train step.

    Trace-side, :meth:`fold` runs INSIDE the donated jit and returns the
    next stats pack plus the skip-guard predicate. Host-side,
    :meth:`after_step` counts batches and fetches the pack on the
    EVERY_N cadence; :meth:`fetch` is the one sanctioned D2H.
    """

    def __init__(self, names, sizes, monitor=None):
        self.names = list(names)
        self.sizes = [max(int(s), 1) for s in sizes]
        self.n = len(self.names)
        guard = str(_env.get("MXNET_TPU_NUMWATCH_GUARD") or "")
        modes = {m.strip() for m in guard.split(",") if m.strip()}
        unknown = modes - {"skip", "rollback"}
        if unknown:
            raise ValueError(
                "MXNET_TPU_NUMWATCH_GUARD=%r: unknown action(s) %s "
                "(valid: skip, rollback)" % (guard, sorted(unknown)))
        self.skip_guard = "skip" in modes
        self.rollback_guard = "rollback" in modes
        self._every_n = max(1, int(_env.get("MXNET_TPU_NUMWATCH_EVERY_N")))
        self._max_skips = int(_env.get("MXNET_TPU_NUMWATCH_MAX_SKIPS"))
        self._cooldown = int(
            _env.get("MXNET_TPU_NUMWATCH_ROLLBACK_COOLDOWN"))
        self._monitor = monitor
        self._pack = None            # the donated device array
        self._host_step = 0
        self._loss_available = False
        self._known_skips = 0
        self._rollbacks = 0
        self._last_body = None       # host copy of the last fetch
        self._last_extras = None
        self._last_prov = None
        self._ckpt = None
        self._last_rollback_step = None
        self._skip_cap_hit = False
        self._warned_no_ckpt = False

    # -- trace-side ---------------------------------------------------------
    @property
    def trace_key(self):
        """Joins the fused step's jit-cache key: arming the plane or its
        skip guard changes the traced computation."""
        return ("numwatch", self.skip_guard)

    def device_pack(self, like):
        """The donated stats pack for the next dispatch — zeroed on
        first use (replicated on ``like``'s mesh so the jit sees one
        consistent device set), thereafter whatever the last write-back
        swapped in. Caller holds an ``intentional_transfer`` window."""
        if self._pack is None:
            import jax
            import jax.numpy as jnp

            z = jnp.zeros((self.n + 1, NCOLS), jnp.float32)
            sharding = getattr(like, "sharding", None)
            if sharding is not None:
                try:
                    from jax.sharding import NamedSharding, PartitionSpec

                    if isinstance(sharding, NamedSharding):
                        z = jax.device_put(
                            z, NamedSharding(sharding.mesh,
                                             PartitionSpec()))
                except Exception:
                    pass
            self._pack = z
        return self._pack

    def write_back(self, new_pack):
        """Install the dispatch's output pack (the old one was donated)."""
        self._pack = new_pack

    def reset_pack(self):
        """Drop the pack (fresh zeros next step). Used after a rollback:
        the sticky first_bad_* stamps describe the abandoned timeline."""
        self._pack = None
        self._known_skips = 0
        self._last_body = None
        self._last_prov = None
        self._skip_cap_hit = False

    def fold(self, pack, p_vals, grads, new_p, outs, labels):
        """Fold this step's numerics into the stats pack — traced INSIDE
        the fused jit; the plane never costs a second dispatch. All
        reductions are small (one scalar row per param), so XLA fuses
        them into the backward/update computation it already runs.
        Returns ``(new_pack, grads_ok)``: ``grads_ok`` is a traced
        scalar bool, True iff every gradient is finite — the skip
        guard's select predicate."""
        import jax.numpy as jnp

        f32 = jnp.float32
        step_no = pack[self.n, M_STEP] + 1.0
        rows = []
        bad_any = jnp.bool_(False)
        for i in range(self.n):
            g32 = grads[i].astype(f32)
            w32 = p_vals[i].astype(f32)
            g_fin = jnp.isfinite(g32)
            g_nonfin = jnp.sum(~g_fin).astype(f32)
            g_safe = jnp.where(g_fin, g32, 0.0)
            g_sumsq = jnp.sum(g_safe * g_safe)
            g_maxabs = jnp.max(jnp.abs(g_safe))
            g_zero = jnp.sum((g32 == 0).astype(f32))
            w_fin = jnp.isfinite(w32)
            w_nonfin = jnp.sum(~w_fin).astype(f32)
            w_safe = jnp.where(w_fin, w32, 0.0)
            w_sumsq = jnp.sum(w_safe * w_safe)
            upd = new_p[i].astype(f32) - w32
            u_safe = jnp.where(jnp.isfinite(upd), upd, 0.0)
            upd_sumsq = jnp.sum(u_safe * u_safe)
            fb_p = pack[i, FB_PARAM]
            fb_p = jnp.where((w_nonfin > 0) & (fb_p == 0), step_no, fb_p)
            fb_g = pack[i, FB_GRAD]
            fb_g = jnp.where((g_nonfin > 0) & (fb_g == 0), step_no, fb_g)
            rows.append(jnp.stack([g_sumsq, g_maxabs, g_nonfin, g_zero,
                                   w_sumsq, w_nonfin, upd_sumsq,
                                   fb_p, fb_g]))
            bad_any = bad_any | (g_nonfin > 0)
        grads_ok = ~bad_any

        # META row: in-graph loss (mean NLL against the first label when
        # the head is a 2-d probability output — the SoftmaxOutput
        # family), output nonfinite count, and the in-graph skip counter
        loss = jnp.zeros((), f32)
        self._loss_available = False
        out0 = outs[0] if outs else None
        lab0 = labels[0] if labels else None
        if out0 is not None and lab0 is not None \
                and getattr(out0, "ndim", 0) == 2 \
                and getattr(lab0, "ndim", 0) == 1 \
                and jnp.issubdtype(out0.dtype, jnp.inexact):
            p = out0.astype(f32)
            idx = jnp.clip(lab0.astype(jnp.int32), 0, p.shape[1] - 1)
            picked = jnp.take_along_axis(p, idx[:, None], axis=1)[:, 0]
            loss = -jnp.mean(jnp.log(jnp.maximum(picked, 1e-12)))
            self._loss_available = True
        out_nonfin = jnp.zeros((), f32)
        if out0 is not None and jnp.issubdtype(out0.dtype, jnp.inexact):
            out_nonfin = jnp.sum(~jnp.isfinite(out0.astype(f32))) \
                .astype(f32)
        skips = pack[self.n, M_SKIPS]
        if self.skip_guard:
            skips = skips + jnp.where(grads_ok, 0.0, 1.0)
        meta = jnp.concatenate([
            jnp.stack([step_no, loss, out_nonfin, skips]),
            jnp.zeros((NCOLS - len(META),), f32)])
        new_pack = jnp.stack(rows + [meta])
        return new_pack, grads_ok

    # -- host-side ----------------------------------------------------------
    def bind_ckpt(self, manager):
        """Give the rollback guard its CheckpointManager (fit wires the
        one it builds from MXNET_TPU_CKPT_DIR; manual drivers may bind
        their own)."""
        self._ckpt = manager

    def after_step(self):
        """Per-batch host hook: count the step; on the EVERY_N cadence
        fetch the pack and return the step-record extras dict (None on
        off-cadence steps)."""
        self._host_step += 1
        if self._pack is None or self._host_step % self._every_n:
            return None
        return self.fetch()

    def fetch(self):
        """One small D2H of the stats pack inside an intentional-
        transfer window — telemetry, the health ring, provenance, and
        the guard actions all update from this single copy."""
        if self._pack is None:
            return None
        import jax

        with _san.intentional_transfer():
            pack = np.asarray(
                jax.device_get(self._pack))  # graft: host-sync
        return self._ingest(pack)

    def _ingest(self, pack):
        n = self.n
        body = pack[:n]
        meta = pack[n]
        self._last_body = body
        grad_norm = float(np.sqrt(max(float(body[:, G_SUMSQ].sum()), 0.0)))
        nonfinite = int(body[:, G_NONFIN].sum() + body[:, W_NONFIN].sum())
        uw_max = 0.0
        for i in range(n):
            w_sq = float(body[i, W_SUMSQ])
            u_sq = float(body[i, UPD_SUMSQ])
            if w_sq > 0.0:
                uw_max = max(uw_max, math.sqrt(u_sq / w_sq))
        loss = float(meta[M_LOSS]) if self._loss_available else None
        skips = int(meta[M_SKIPS])
        self._last_prov = self._provenance(body)

        _tel.inc("numwatch.fetches")
        _tel.set_gauge("numwatch.grad_norm", grad_norm)
        _tel.set_gauge("numwatch.uw_max", uw_max)
        _tel.set_gauge("numwatch.nonfinite", float(nonfinite))
        if loss is not None:
            _tel.set_gauge("numwatch.loss", loss)
        d_skips = skips - self._known_skips
        if d_skips > 0:
            _tel.inc("numwatch.skipped_steps", d_skips)
        self._known_skips = skips

        extras = {"numwatch_grad_norm": grad_norm,
                  "numwatch_uw_max": uw_max,
                  "numwatch_nonfinite": nonfinite,
                  "numwatch_skips": skips,
                  "numwatch_rollbacks": self._rollbacks}
        if loss is not None:
            extras["numwatch_loss"] = loss
        if self._last_prov is not None:
            extras["numwatch_bad_tensor"] = self._last_prov[0]

        self._guard(body, meta, extras)

        _HEALTH_RING.append({
            "step": int(meta[M_STEP]), "host_step": self._host_step,
            "loss": loss, "grad_norm": grad_norm, "uw_max": uw_max,
            "nonfinite": nonfinite,
            "bad_tensor": (None if self._last_prov is None
                           else self._last_prov[0]),
            "skips": skips, "rollbacks": self._rollbacks})
        self._last_extras = extras
        return extras

    def _provenance(self, body):
        """Name the first tensor to go bad from the sticky first_bad_*
        stamps: earliest step wins; at equal step a nonfinite PARAM
        beats a nonfinite GRAD (one backward pass fans a single NaN out
        to every gradient in the same step, so the grad stamps alone
        can't localize); remaining ties break in forward order.
        Returns (name, kind, step) or None."""
        best = None
        for i in range(self.n):
            for kind_rank, col, kind in ((0, FB_PARAM, "param"),
                                         (1, FB_GRAD, "grad")):
                s = float(body[i, col])
                if s <= 0:
                    continue
                key = (s, kind_rank, i)
                if best is None or key < best[0]:
                    best = (key, (self.names[i], kind, int(s)))
        return None if best is None else best[1]

    def provenance(self):
        """(name, kind, step) of the first tensor to go nonfinite, from
        the last fetch — None while the model is healthy."""
        return self._last_prov

    # -- guard actions ------------------------------------------------------
    def _guard(self, body, meta, extras):
        escalate = False
        skips = int(meta[M_SKIPS])
        if self.skip_guard and skips > self._max_skips \
                and not self._skip_cap_hit:
            self._skip_cap_hit = True
            _tel.inc("numwatch.skip_cap_exceeded")
            _log.error(
                "numwatch: skip guard dropped %d steps (cap %d) — the "
                "model is not recovering%s", skips, self._max_skips,
                "; escalating to rollback" if self.rollback_guard
                else "")
            escalate = self.rollback_guard
        if not self.rollback_guard:
            return
        if self._ckpt is None:
            if not self._warned_no_ckpt:
                self._warned_no_ckpt = True
                _log.warning(
                    "numwatch: rollback guard armed but no "
                    "CheckpointManager is bound (set MXNET_TPU_CKPT_DIR "
                    "or call bind_ckpt); the guard is inert")
            return
        params_bad = float(body[:, W_NONFIN].sum()) > 0
        if params_bad or escalate:
            self._rollback(extras)
        else:
            # a clean fetch is the rollback target: persist it so the
            # guard never restores a poisoned periodic snapshot
            self._ckpt.save_now("healthy")

    def _rollback(self, extras):
        last = self._last_rollback_step
        if last is not None and self._host_step - last < self._cooldown:
            raise NumericsError(
                "numwatch: model nonfinite again %d steps after a "
                "rollback (cooldown %d) — refusing to thrash the "
                "snapshot store; lower the lr or fix the data"
                % (self._host_step - last, self._cooldown))
        info = self._ckpt.rollback("numwatch")
        if info is None:
            _log.error("numwatch: rollback requested but the snapshot "
                       "store holds no restorable snapshot")
            return
        self._rollbacks += 1
        self._last_rollback_step = self._host_step
        _tel.inc("numwatch.rollbacks")
        self.reset_pack()
        extras["numwatch_rollback"] = True
        extras["numwatch_rollbacks"] = self._rollbacks
        _log.warning(
            "numwatch: nonfinite params — rolled back to the last "
            "healthy snapshot (saved at step %s); rollback #%d",
            info.get("step"), self._rollbacks)

    # -- monitor facade feed ------------------------------------------------
    def monitor_rows(self, re_prog, step):
        """Serve the classic Monitor rows — ``(step, name, stat)`` with
        the default ``norm(x)/sqrt(x.size)`` stat for every param and
        its ``_grad`` twin matching ``re_prog`` — from a fresh fetch of
        the pack: no executor callback, no fused fallback, one D2H."""
        self.fetch()
        if self._last_body is None:
            return []
        body = self._last_body
        rows = []
        for i, name in enumerate(self.names):
            sz = self.sizes[i]
            if re_prog.match(name):
                stat = math.sqrt(max(float(body[i, W_SUMSQ]), 0.0) / sz)
                rows.append((step, name, "%f" % stat))
            if re_prog.match(name + "_grad"):
                stat = math.sqrt(max(float(body[i, G_SUMSQ]), 0.0) / sz)
                rows.append((step, name + "_grad", "%f" % stat))
        return rows
