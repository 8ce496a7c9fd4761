"""Module: the standard trainable unit over one symbol
(reference ``python/mxnet/module/module.py:39``)."""
from __future__ import annotations

import logging
from typing import Dict, List, Optional

from ..base import MXNetError
from ..context import Context, cpu
from ..initializer import Uniform
from ..io import DataDesc
from .. import ndarray as nd
from .. import optimizer as opt
from ..kvstore import KVStore
from ..kvstore import create as _create_kvstore
from .base_module import BaseModule
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None,
                 fixed_param_names=None):
        super().__init__(logger=logger)
        if context is None:
            context = cpu()
        if isinstance(context, Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list
        self._symbol = symbol
        self._data_names = list(data_names)
        self._label_names = list(label_names or [])
        self._fixed_param_names = list(fixed_param_names or [])

        arg_names = symbol.list_arguments()
        input_names = self._data_names + self._label_names
        self._param_names = [n for n in arg_names if n not in input_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        self._arg_params: Optional[Dict[str, nd.NDArray]] = None
        self._aux_params: Optional[Dict[str, nd.NDArray]] = None
        self._shared_owner: Optional["Module"] = None
        self._params_dirty = False
        self._exec_group: Optional[DataParallelExecutorGroup] = None
        self._optimizer = None
        self._kvstore = None
        self._updater = None
        self._update_on_kvstore = False

    # -- properties --------------------------------------------------------
    @property
    def _params_dirty(self) -> bool:
        """Device-params-newer-than-host flag, routed through the module
        that OWNS the shared param arrays. Modules bound with
        ``shared_module=`` share executor-tier NDArrays and the host
        ``_arg_params`` dicts with the owner, so dirtiness is a property
        of the owner's training activity — a by-value snapshot at bind
        time would let a non-active bucket module hand out stale host
        params after the owner trains."""
        owner = getattr(self, "_shared_owner", None)
        if owner is not None:
            return owner._params_dirty
        return getattr(self, "_params_dirty_flag", False)

    @_params_dirty.setter
    def _params_dirty(self, value: bool):
        owner = getattr(self, "_shared_owner", None)
        if owner is not None:
            owner._params_dirty = value
        else:
            self._params_dirty_flag = bool(value)

    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        return self._data_shapes

    @property
    def label_shapes(self):
        return self._label_shapes

    @property
    def output_shapes(self):
        shapes = {d.name: d.shape for d in self._data_shapes}
        shapes.update({d.name: d.shape for d in self._label_shapes})
        _, out_shapes, _ = self._symbol.infer_shape(**shapes)
        return list(zip(self._output_names, out_shapes))

    # -- bind --------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if force_rebind:
            self._exec_group = None
            self.binded = False
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad

        self._data_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                             for d in data_shapes]
        self._label_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                              for d in (label_shapes or [])]

        shared_group = shared_module._exec_group if shared_module else None
        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group,
            logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req)
        self.binded = True
        if shared_module is not None and shared_module.params_initialized:
            # weight sharing happens at the executor tier: _bind_exec reused
            # the shared group's param NDArrays directly. Do NOT set_params
            # from the module-level host copies here — they go stale the
            # moment update() runs (only get_params syncs them back), so
            # copying them in would reset trained weights on every
            # new-bucket bind.
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
            # dirty tracking routes through the OWNING module (chase one
            # level so chains share a single root): when the owner
            # trains, every sharing module sees fresh dirtiness instead
            # of a stale bind-time snapshot
            self._shared_owner = getattr(shared_module, "_shared_owner",
                                         None) or shared_module
            self.params_initialized = True
        elif self.params_initialized:
            # params loaded before bind (Module.load path)
            self._exec_group.set_params(self._arg_params, self._aux_params)

    # -- params ------------------------------------------------------------
    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        if self.params_initialized and not force_init:
            return
        if not self.binded:
            raise MXNetError("call bind before init_params")

        shapes = {d.name: d.shape for d in self._data_shapes}
        shapes.update({d.name: d.shape for d in self._label_shapes})
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**shapes)
        arg_shape_map = dict(zip(self._symbol.list_arguments(), arg_shapes))
        aux_shape_map = dict(zip(self._aux_names, aux_shapes))

        if self._arg_params is None:
            self._arg_params = {n: nd.zeros(arg_shape_map[n])
                                for n in self._param_names}
        if self._aux_params is None:
            # in the dtype they are bound in (an op may count in int32):
            # a float32 copy set over them would make step 2 another
            # program than step 1
            bound = self._exec_group.executor.aux_dict
            self._aux_params = {n: nd.zeros(aux_shape_map[n],
                                            dtype=bound[n].dtype)
                                for n in self._aux_names}

        for name, arr in self._arg_params.items():
            if arg_params is not None and name in arg_params:
                arr[:] = arg_params[name]
            elif arg_params is not None and not allow_missing:
                raise MXNetError("missing arg_param '%s' (pass "
                                 "allow_missing=True to initialize it)" % name)
            elif initializer is not None:
                initializer(name, arr)
        for name, arr in self._aux_params.items():
            if aux_params is not None and name in aux_params:
                arr[:] = aux_params[name]
            elif initializer is not None:
                initializer(name, arr)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params)

    def get_params(self):
        if self._params_dirty:
            self._sync_params_from_devices()
        return self._arg_params, self._aux_params

    def _sync_params_from_devices(self):
        self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    # -- optimizer ---------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        if not self.binded or not self.params_initialized:
            raise MXNetError("bind and init_params before init_optimizer")
        if self.optimizer_initialized and not force_init:
            return

        if isinstance(kvstore, str):
            kvstore = _create_kvstore(kvstore) if kvstore else None
        self._kvstore = kvstore
        # lr normalization (reference module.py:306-307: batch_size scaled
        # by num_workers under dist kvstore)
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        idx2name = {i: n for i, n in enumerate(self._param_names)}
        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self._symbol,
                                   param_idx2name=idx2name, **optimizer_params)
        self._optimizer = optimizer
        self._updater = opt.get_updater(optimizer)
        # update_on_kvstore: push grad / pull weight with server-side update
        self._update_on_kvstore = bool(kvstore) and "dist" in (kvstore.type if kvstore else "")
        if self._update_on_kvstore and getattr(
                kvstore, "fused_step_compatible", False):
            # a dist store whose exchange the fused step can subsume
            # (single-process dist_sync) keeps the update worker-side so
            # the in-jit path stays eligible — the server-side update
            # would force the kvstore_update fallback for no byte saved
            self._update_on_kvstore = False
        if kvstore:
            for i, name in enumerate(self._param_names):
                kvstore.init(i, self._arg_params[name])
            if self._update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
        self.optimizer_initialized = True

    # -- compute -----------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        if not self.binded or not self.params_initialized:
            raise MXNetError("module not initialized")
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        self._exec_group.backward(out_grads)

    def update(self):
        """Apply the optimizer to the accumulated gradients (reference
        ``Module.update``: kvstore push/pull or local updater)."""
        if not self.optimizer_initialized:
            raise MXNetError("init_optimizer before update")
        self._params_dirty = True
        group = self._exec_group
        if self._kvstore and self._update_on_kvstore:
            for i, name in enumerate(self._param_names):
                if name not in group.executor.grad_dict:
                    continue
                grad = group.executor.grad_dict[name]
                weight = group.executor.arg_dict[name]
                self._kvstore.push(i, grad, priority=-i)
                self._kvstore.pull(i, weight, priority=-i)
        else:
            # No push/pull round-trip here: with the single fused executor
            # the cross-device grad reduction already happened inside the
            # training step (GSPMD all-reduce), so the local grads ARE the
            # reduced grads — the reference's _update_params push/pull
            # (model.py:96) is subsumed. All params update in ONE fused
            # dispatch (Updater.update_multi) rather than one per param.
            items = [(i, group.executor.grad_dict[name],
                      group.executor.arg_dict[name])
                     for i, name in enumerate(self._param_names)
                     if name in group.executor.grad_dict]
            self._updater.update_multi(items)

    def update_metric(self, eval_metric, labels):
        self._exec_group.update_metric(eval_metric, labels)

    def _fused_train_step(self, eval_metric):
        """One-dispatch-per-batch training step (MXNET_TPU_FUSED_STEP=1)
        or None when the configuration can't fuse — see
        :func:`mxnet_tpu.fused_step.make_fused_step` for the gates."""
        from ..fused_step import make_fused_step

        fused = make_fused_step(self, eval_metric)
        self._fused_step_active = fused is not None
        if fused is not None:
            self.logger.info(
                "fused train step active: forward+backward+update%s "
                "compiled into one donated XLA dispatch per batch",
                "+metric" if fused._fold_leaves is not None else "")
            # the fused step never materialises a gradient: the buffers
            # bound for them (a float32 copy of every parameter) leave
            # the device for the host, and their memory is the step's
            self._exec_group.release_grad_buffers()
        return fused

    def get_outputs(self, merge_multi_context=True):
        return self._exec_group.get_outputs()

    def publish_aux_counters(self):
        """What only a fence can read, into telemetry: the memory of the
        step's first device, ONE ``memory_stats()`` call, as the gauges
        ``device.hbm_in_use_bytes``, ``device.hbm_reserved_bytes`` and
        ``device.hbm_limit_bytes``, set together (none of them where the
        backend has no allocator to ask: the CPU), and the auxiliary
        states that COUNT on the device (a routed-expert layer's rows
        per expert). The step never fetches those; this does, so call it
        at a fence only: ``fit`` does after set-up (the baseline) and at
        each epoch's end, a harness at the ends of its window. Counters
        get the increment since the last call, summed over the layers; a
        gauge its largest value over the layers. A no-op with telemetry
        off."""
        from .. import telemetry as _tel
        from .. import xprof as _xprof

        if not _tel.enabled() or not self.binded:
            return
        hbm = _xprof.hbm_stats(self._context[0].jax_device())
        if hbm["source"] == "memory_stats":
            for gauge, key in (("in_use", "live_bytes"),
                               ("reserved", "reserved_bytes"),
                               ("limit", "limit_bytes")):
                if hbm[key] is not None:
                    _tel.set_gauge("device.hbm_%s_bytes" % gauge, hbm[key])
        aux = self._exec_group.executor.aux_dict
        last = self.__dict__.setdefault("_aux_counter_last", {})
        gauges = {}
        for node in self._symbol._topo():
            read = None if node.is_variable \
                else getattr(node.op, "aux_counters", None)
            if read is None:
                continue
            name = "%s_%s" % (node.name, node.op.list_auxiliary_states()[0])
            now = aux[name].asnumpy()  # graft: host-sync
            before = last.get(name)
            last[name] = now
            if before is None:
                continue                     # the baseline
            counters, layer_gauges = read(before, now)
            for key, n in counters.items():
                _tel.inc(key, n)
            for key, v in layer_gauges.items():
                gauges[key] = max(v, gauges.get(key, v))
        for key, v in gauges.items():
            _tel.set_gauge(key, v)

    def get_input_grads(self, merge_multi_context=True):
        return self._exec_group.get_input_grads()

    def install_monitor(self, mon):
        if not self.binded:
            raise MXNetError("bind before install_monitor")
        self._exec_group.install_monitor(mon)

    # -- checkpointing -----------------------------------------------------
    def save_checkpoint(self, prefix: str, epoch: int,
                        save_optimizer_states: bool = False):
        self._symbol.save("%s-symbol.json" % prefix)
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            # crash-safe like the param file: tmp + fsync + os.replace
            from ..checkpoint import atomic_write_bytes
            atomic_write_bytes(
                state_name,
                self._updater.get_states() if self._updater else b"")

    def load_optimizer_states(self, fname: str):
        if self._updater is None:
            raise MXNetError("init_optimizer before load_optimizer_states")
        with open(fname, "rb") as f:
            blob = f.read()
        try:
            self._updater.set_states(blob)
        except Exception as e:
            raise MXNetError(
                "invalid optimizer-states file %s: %s (partial/torn "
                "write?)" % (fname, e))

    @staticmethod
    def load(prefix: str, epoch: int, load_optimizer_states: bool = False,
             **kwargs) -> "Module":
        from ..model import load_checkpoint
        from .. import symbol as sym

        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        mod = Module(symbol=symbol, **kwargs)
        mod._arg_params = arg_params
        mod._aux_params = aux_params
        mod.params_initialized = True
        return mod
