"""BaseModule: the high-level train/predict interface
(reference ``python/mxnet/module/base_module.py``)."""
from __future__ import annotations

import contextlib as _contextlib
import logging
import time
from collections import namedtuple
from typing import Dict, List, Optional

from ..base import MXNetError
from .. import metric as _metric
from .. import ndarray as nd
from .. import numwatch as _numwatch
from .. import telemetry as _tel
from .. import tracing as _tracing
from ..analysis import sanitizers as _san
from ..initializer import Uniform
from ..io import DataBatch

__all__ = ["BaseModule", "BatchEndParam"]

BatchEndParam = namedtuple("BatchEndParam",
                           ["epoch", "nbatch", "eval_metric", "locals"])

_EPOCH_OVER = object()   # what next() gives fit when the iterator ends


def _as_list(obj):
    if obj is None:
        return []
    if isinstance(obj, (list, tuple)):
        return list(obj)
    return [obj]


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None

    # -- abstract interface ------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        raise NotImplementedError

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError

    def backward(self, out_grads=None):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError

    def get_outputs(self):
        raise NotImplementedError

    def get_params(self):
        raise NotImplementedError

    def get_input_grads(self):
        raise NotImplementedError

    @property
    def data_names(self):
        raise NotImplementedError

    @property
    def output_names(self):
        raise NotImplementedError

    @property
    def data_shapes(self):
        raise NotImplementedError

    @property
    def label_shapes(self):
        raise NotImplementedError

    @property
    def output_shapes(self):
        raise NotImplementedError

    @property
    def symbol(self):
        return self._symbol

    # -- derived convenience (reference base_module.py) --------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    def save_params(self, fname: str):
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        # crash-safe: a preemption mid-save must never leave a torn
        # param file over a good one (tmp + fsync + os.replace)
        from ..checkpoint import atomic_ndarray_save
        atomic_ndarray_save(fname, save_dict)

    def load_params(self, fname: str):
        save_dict = nd.load(fname)
        arg_params, aux_params = {}, {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise MXNetError("invalid param file %s" % fname)
        self.set_params(arg_params, aux_params)

    def _pad_partial_batch(self, eval_batch):
        """Pad-and-slice for the final partial batch: an iterator that
        yields a SMALLER last batch would retrace the compiled forward
        for that one-off shape (a fresh XLA compile to serve a handful
        of rows). Instead the batch axis is padded up to the bound
        batch size with zero rows and ``pad`` is extended, so
        predict/score slice the fake rows back off (``getpad``
        semantics) and every batch reuses the one compiled executable.
        Returns ``(batch, extra_rows)`` — (the original batch, 0) when
        shapes already match."""
        shapes = getattr(self, "_data_shapes", None)
        if not shapes or not eval_batch.data:
            return eval_batch, 0
        bound = shapes[0].shape[0]
        rows = eval_batch.data[0].shape[0]
        if rows >= bound:
            return eval_batch, 0
        extra = bound - rows
        import numpy as np

        def _pad(arrs):
            out = []
            for a in arrs or []:
                h = a.asnumpy() if hasattr(a, "asnumpy") else np.asarray(a)
                out.append(nd.array(np.concatenate(
                    [h, np.zeros((extra,) + h.shape[1:], h.dtype)],
                    axis=0)))
            return out

        _tel.inc("module.pad_batches")
        padded = DataBatch(_pad(eval_batch.data), _pad(eval_batch.label),
                           pad=eval_batch.pad + extra,
                           index=eval_batch.index)
        return padded, extra

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, reset=True, epoch=0):
        if not self.binded or not self.params_initialized:
            raise MXNetError("module must be binded and initialized")
        eval_metric = _metric.create(eval_metric)
        if reset:
            eval_data.reset()
        eval_metric.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            padded, extra = self._pad_partial_batch(eval_batch)
            self.forward(padded, is_train=False)
            if extra:
                # metric must only see the real rows: slice the padded
                # outputs and pair them with the ORIGINAL labels — same
                # numbers the per-shape retrace used to produce
                outs = [out[0:out.shape[0] - extra]
                        for out in self.get_outputs()]
                eval_metric.update(eval_batch.label, outs)
            else:
                self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                       eval_metric=eval_metric, locals=locals())
                for cb in _as_list(batch_end_callback):
                    cb(params)
        return eval_metric.get_name_value()

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        if not self.binded or not self.params_initialized:
            raise MXNetError("module must be binded and initialized")
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            padded, _ = self._pad_partial_batch(eval_batch)
            self.forward(padded, is_train=False)
            pad = padded.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                if len(out) != num_outputs:
                    raise MXNetError("output count changed across batches")
            output_list2 = [nd.concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            padded, _ = self._pad_partial_batch(eval_batch)
            self.forward(padded, is_train=False)
            pad = padded.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield outputs, nbatch, eval_batch

    def publish_aux_counters(self):
        """Telemetry from what auxiliary states count on the device, read
        at a fence (``Module.publish_aux_counters``); nothing by default."""

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None):
        """The training loop (reference ``base_module.py:275`` fit)."""
        if num_epoch is None:
            raise MXNetError("num_epoch must be specified")
        with _tel.span("fit.bind"):
            self.bind(data_shapes=train_data.provide_data,
                      label_shapes=train_data.provide_label,
                      for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        with _tel.span("fit.init_params"):
            self.init_params(initializer=initializer,
                             arg_params=arg_params, aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init)
        with _tel.span("fit.init_optimizer"):
            self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                                optimizer_params=optimizer_params)
        if validation_metric is None:
            validation_metric = eval_metric
        eval_metric = _metric.create(eval_metric)

        # MXNET_TPU_FEED_DEPTH=N: a worker thread keeps N staged batches
        # in flight and io.feed_stall_ms records how long each step
        # blocked waiting for input (StepTrace's input-starved signal).
        # Falls back to MXNET_TPU_DEVICE_STAGING=1 single-batch double
        # buffering: device_put batch N+1 while step N executes, so H2D
        # overlaps compute instead of serializing with it.
        from ..io_pipeline import (maybe_wrap_device_staging,
                                   maybe_wrap_feed_scheduler)
        # the bound executor group (when this module has one) makes the
        # staging wrappers mesh-aware: batches land dp-sharded, so the
        # sharded fused step re-handles them instead of resharding
        _group = getattr(self, "_exec_group", None)
        train_data = maybe_wrap_feed_scheduler(train_data, group=_group)
        train_data = maybe_wrap_device_staging(train_data, group=_group)

        # env-driven observability (metrics server, flight recorder);
        # single flag check when telemetry is off
        _tracing.maybe_init()

        # MXNET_TPU_FUSED_STEP=1: fwd+bwd+update(+metric fold) compiled
        # into ONE donated XLA dispatch per batch; None falls back to
        # the classic three-phase loop (dist kvstores, custom-update
        # optimizers, monitors, grad_req="add")
        with _tel.span("fit.fused_build"):
            fused = self._fused_train_step(eval_metric)
        self.publish_aux_counters()   # the baseline of what the device counts

        # MXNET_TPU_CKPT_DIR: preemption-safe full-state snapshots —
        # periodic saves every MXNET_TPU_CKPT_EVERY_N_STEPS, auto-resume
        # from the newest valid snapshot, and a SIGTERM grace path that
        # checkpoints at the step boundary before exiting
        from ..checkpoint import maybe_manager as _ckpt_manager
        ckpt = _ckpt_manager(self, eval_metric, train_data)
        resume = ckpt.maybe_restore() if ckpt is not None else None
        if ckpt is not None:
            ckpt.arm()
        # the numerics plane (MXNET_TPU_NUMWATCH / a routed Monitor)
        # rides the fused step; its rollback guard restores through the
        # same manager the preemption path uses
        numwatch = getattr(fused, "_numwatch", None)
        if numwatch is not None and ckpt is not None:
            numwatch.bind_ckpt(ckpt)
        try:
            self._fit_epochs(train_data, eval_data, eval_metric,
                             validation_metric, epoch_end_callback,
                             batch_end_callback, eval_batch_end_callback,
                             monitor, fused, ckpt, resume,
                             begin_epoch, num_epoch, numwatch)
        finally:
            if ckpt is not None:
                ckpt.disarm()

    def _fit_epochs(self, train_data, eval_data, eval_metric,
                    validation_metric, epoch_end_callback,
                    batch_end_callback, eval_batch_end_callback,
                    monitor, fused, ckpt, resume, begin_epoch, num_epoch,
                    numwatch=None):
        for epoch in range(begin_epoch, num_epoch):
            if resume is not None and epoch < resume["epoch"]:
                continue
            # resuming mid-epoch: metric sums and the data cursor were
            # restored by the snapshot — reset would discard them
            resuming = resume is not None and epoch == resume["epoch"]
            nbatch_base = resume["nbatch"] + 1 if resuming else 0
            resume = None
            tic = time.time()
            if not resuming:
                eval_metric.reset()
                train_data.reset()
            nbatch = nbatch_base - 1
            # MXNET_TPU_SANITIZE=transfer (fused path only: the classic
            # loop updates metrics host-side by design): any implicit
            # host<->device transfer inside the step loop raises at the
            # batch that caused it; sanctioned marshalling sits inside
            # intentional_transfer() windows
            guard = (_san.step_guard() if fused is not None
                     else _contextlib.nullcontext())
            batches = iter(train_data)
            try:
                with guard:
                    while True:
                        _tel.next_step()
                        # one iteration, boundary to boundary: the data
                        # fetch (where input stalls accrue) belongs to
                        # the step that waited on it
                        with _tel.span("fit.step") as step_span:
                            with _tel.span("fit.next"):
                                data_batch = next(batches, _EPOCH_OVER)
                            if data_batch is _EPOCH_OVER:
                                step_span.cancel()
                                break
                            nbatch += 1
                            self._fit_step(
                                data_batch, epoch, nbatch, eval_metric,
                                fused, ckpt, numwatch, monitor,
                                batch_end_callback, step_span)
            except Exception as e:
                if _san.is_transfer_guard_error(e):
                    _san.record_trip("transfer")
                raise
            if batch_end_callback is not None and nbatch >= 0:
                # callbacks with an epoch_end hook (Speedometer) get to
                # report their partial tail window instead of dropping it
                params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                       eval_metric=eval_metric,
                                       locals=locals())
                for cb in _as_list(batch_end_callback):
                    ep_end = getattr(cb, "epoch_end", None)
                    if callable(ep_end):
                        ep_end(params)
            for name, val in eval_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                             time.time() - tic)
            arg_params_, aux_params_ = self.get_params()
            self.publish_aux_counters()
            if epoch_end_callback is not None:
                for cb in _as_list(epoch_end_callback):
                    cb(epoch, self.symbol, arg_params_, aux_params_)
            if eval_data is not None:
                res = self.score(eval_data, validation_metric,
                                 batch_end_callback=eval_batch_end_callback,
                                 epoch=epoch)
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f",
                                     epoch, name, val)

    def _fit_step(self, data_batch, epoch, nbatch, eval_metric, fused,
                  ckpt, numwatch, monitor, batch_end_callback, step_span):
        """One batch of ``fit``: the step, then what runs at its end."""
        if monitor is not None:
            monitor.tic()
        if ckpt is not None:
            # SIGTERM inside this window defers to the step boundary
            # (donated packs are torn mid-dispatch)
            ckpt.step_begin()
        if fused is not None:
            fused.step(data_batch, eval_metric)
        else:
            # device-feed batches (batch.aug) are materialized eagerly
            # inside load_data_batch on this path
            with _tel.span("fit.forward_backward"):
                self.forward_backward(data_batch)
            with _tel.span("fit.update"):
                self.update()
            with _tel.span("fit.update_metric"):
                self.update_metric(eval_metric, data_batch.label)
        with _tel.span("fit.callbacks"):
            if ckpt is not None:
                # packs whole again: periodic cadence save, or the
                # deferred preempt save + exit
                ckpt.step_end(epoch, nbatch)
            # numerics plane: one None check when disabled; on the
            # EVERY_N cadence a single small D2H fetch of the stats
            # pack plus guard actions
            nw_extra = _numwatch.after_step(numwatch)
            if monitor is not None:
                monitor.toc_print()
            if batch_end_callback is not None:
                params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                       eval_metric=eval_metric,
                                       locals=locals())
                for cb in _as_list(batch_end_callback):
                    cb(params)
            if _tel.enabled():
                extra = {"epoch": epoch, "nbatch": nbatch}
                if nw_extra:
                    extra.update(nw_extra)
                # the step's last act, so that the latency is the
                # fit.step span's but for this record itself
                _tracing.record_step(step_span.elapsed_ms(), extra=extra)

    def _fused_train_step(self, eval_metric):
        """Hook: an object with ``.step(data_batch, eval_metric)`` that
        runs one batch as a single fused dispatch, or None to use the
        classic forward_backward/update/update_metric loop. Module
        overrides this; the base has no fused path."""
        return None

    def install_monitor(self, mon):
        raise NotImplementedError

    def get_symbol(self):
        return self._symbol
