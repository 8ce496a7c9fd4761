"""Data-parallel executor group.

TPU-native re-design of the reference's ``DataParallelExecutorGroup``
(``python/mxnet/module/executor_group.py:68-530``): where the reference
slices the batch across per-device executors and reduces grads via
KVStore/Comm, here there is ONE executor whose arrays carry
``jax.sharding`` placements over a named multi-axis device mesh — data
batch-sharded along the data axes (``dp``, and ``fsdp`` when
``MXNET_TPU_MESH_FSDP`` factors the grid), parameters replicated on a
``dp`` mesh or ZeRO-style sharded along ``fsdp`` under the FSDP recipe
(:meth:`param_sharding`). XLA GSPMD partitions the jitted step and
inserts the collectives over ICI automatically — gradient all-reduce
for replicated params, all-gather before the forward plus
reduce-scatter of the grads for sharded ones (the ``kvstore='tpu_sync'``
north star: the exchange fused INTO the training step instead of a
separate push/pull phase).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from ..base import MXNetError
from ..context import Context
from ..executor import Executor
from ..io import DataDesc
from ..ndarray import NDArray

__all__ = ["DataParallelExecutorGroup"]


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts: Sequence[Context], workload,
                 data_shapes, label_shapes, param_names: List[str],
                 for_training: bool, inputs_need_grad: bool,
                 shared_group: Optional["DataParallelExecutorGroup"] = None,
                 logger=None, fixed_param_names: Optional[List[str]] = None,
                 grad_req: str = "write"):
        self.symbol = symbol
        self.contexts = list(contexts)
        self.param_names = param_names
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = set(fixed_param_names or [])
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()

        self.data_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                            for d in data_shapes]
        self.label_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                             for d in (label_shapes or [])]
        self.data_names = [d.name for d in self.data_shapes]
        self.label_names = [d.name for d in self.label_shapes]
        self.batch_size = self.data_shapes[0].shape[
            DataDesc.get_batch_axis(self.data_shapes[0].layout)]

        self._mesh = None
        self._param_shardings: Dict[str, object] = {}
        self._arg_shape: Dict[str, tuple] = {}
        if len(self.contexts) > 1:
            if self.batch_size % len(self.contexts):
                raise MXNetError(
                    "batch size %d not divisible by %d devices"
                    % (self.batch_size, len(self.contexts)))
            self._mesh = self._make_mesh()
        from .. import env as _env
        self._fsdp_params = bool(_env.get("MXNET_TPU_FSDP_PARAMS"))

        # grad requests (reference: data grads only if inputs_need_grad)
        reqs: Dict[str, str] = {}
        for name in self.arg_names:
            if name in self.data_names:
                reqs[name] = "write" if inputs_need_grad else "null"
            elif name in self.label_names or not for_training \
                    or name in self.fixed_param_names:
                reqs[name] = "null"
            else:
                reqs[name] = grad_req
        self.grad_req = reqs

        self._bind_exec(shared_group)

    # ------------------------------------------------------------------
    def _make_mesh(self):
        # one shared mesh constructor (parallel/sharding.py) so the
        # module path and the explicit-sharding API agree on axis names
        # and device-count validation — the fused step's in-jit gradient
        # exchange keys off this mesh's data axes. MXNET_TPU_MESH_FSDP=N
        # factors the device grid into the named (dp, fsdp) mesh; the
        # axis list stays open for tp/pp/ep recipes later.
        from .. import env as _env
        from ..parallel.sharding import make_mesh

        devices = [c.jax_device() for c in self.contexts]
        n = len(devices)
        fsdp = int(_env.get("MXNET_TPU_MESH_FSDP") or 0)
        if fsdp > 1:
            if n % fsdp:
                raise MXNetError(
                    "MXNET_TPU_MESH_FSDP=%d does not divide the %d-device"
                    " grid: the (dp, fsdp) mesh needs dp = devices/fsdp "
                    "to be a whole number" % (fsdp, n))
            return make_mesh({"dp": n // fsdp, "fsdp": fsdp},
                             devices=devices)
        return make_mesh({"dp": n}, devices=devices)

    def _sharding(self, batch_axis: Optional[int]):
        """NamedSharding for a batch-sharded (or replicated, axis None)
        array on the group's mesh. The batch shards over EVERY data
        axis (``dp``, and ``fsdp`` when the mesh carries it), so the
        global batch always splits across all devices."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.sharding import batch_spec

        if self._mesh is None:
            return None
        if batch_axis is None:
            return NamedSharding(self._mesh, P())
        return NamedSharding(self._mesh, batch_spec(self._mesh,
                                                    batch_axis))

    # ------------------------------------------------------------------
    # per-parameter sharding (the FSDP recipe)
    # ------------------------------------------------------------------
    def param_sharding(self, name: str):
        """NamedSharding of param ``name`` (and of its gradient and
        optimizer state): sharded along the mesh's ``fsdp`` axis when
        the recipe is armed and the shape divides, replicated
        otherwise. None on a single-device group. The fused step pins
        the vjp gradients to exactly these shardings, which is what
        makes GSPMD lower the gradient exchange to a reduce-scatter
        (sharded) or all-reduce (replicated) inside the one dispatch."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self._mesh is None:
            return None
        cached = self._param_shardings.get(name)
        if cached is not None:
            return cached
        spec = P()
        shape = self._arg_shape.get(name)
        if shape is not None and name in self.param_names \
                and self._fsdp_params:
            from ..parallel.sharding import fsdp_param_spec

            spec = fsdp_param_spec(shape, self._mesh) or P()
        sharding = NamedSharding(self._mesh, spec)
        self._param_shardings[name] = sharding
        return sharding

    def place_param(self, name: str, np_or_nd, dtype=None) -> NDArray:
        """``device_put`` a param (or same-shaped optimizer-state leaf)
        with its :meth:`param_sharding` — the placement fresh init uses,
        so checkpoint restore re-enters the device bit-identically to a
        cold bind (same avals + shardings -> no retrace)."""
        import jax

        sharding = self.param_sharding(name)
        if sharding is None:
            return self._place(np_or_nd, None, dtype=dtype)
        data = np_or_nd._data if isinstance(np_or_nd, NDArray) \
            else np.asarray(np_or_nd, dtype=dtype)
        return NDArray(jax.device_put(data, sharding),
                       ctx=self.contexts[0])

    def place_like_param(self, name: Optional[str], np_or_nd,
                         dtype=None) -> NDArray:
        """Place an array with ``name``'s param sharding when the shape
        matches the param's (the optimizer-state contract:
        ``_zeros_like_state`` inherits the weight's sharding), else
        replicated — scalar/odd-shaped state leaves replicate."""
        shape = self._arg_shape.get(name) if name else None
        arr = np_or_nd._data if isinstance(np_or_nd, NDArray) \
            else np.asarray(np_or_nd, dtype=dtype)
        if shape is not None and tuple(arr.shape) == tuple(shape):
            return self.place_param(name, np_or_nd, dtype=dtype)
        return self._place(np_or_nd, None, dtype=dtype)

    def _place(self, np_or_nd, batch_axis: Optional[int], dtype=None) -> NDArray:
        import jax

        if isinstance(np_or_nd, NDArray):
            data = np_or_nd._data
        else:
            data = np.asarray(np_or_nd, dtype=dtype)
        sharding = self._sharding(batch_axis)
        if sharding is None:
            dev = self.contexts[0].jax_device()
            return NDArray(jax.device_put(data, dev), ctx=self.contexts[0])
        return NDArray(jax.device_put(data, sharding), ctx=self.contexts[0])

    def _bind_exec(self, shared_group):
        shapes = {d.name: d.shape for d in self.data_shapes}
        shapes.update({d.name: d.shape for d in self.label_shapes})
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**shapes)
        self._arg_shape = {n: tuple(s) for n, s in zip(self.arg_names,
                                                       arg_shapes)}

        shared_args = {}
        if shared_group is not None:
            shared_args = dict(zip(shared_group.arg_names,
                                   shared_group.executor.arg_arrays))

        args, grads = [], {}
        for name, shape in zip(self.arg_names, arg_shapes):
            is_data = name in self.data_names or name in self.label_names
            baxis = self._batch_axis_of(name) if is_data else None
            if name in shared_args and shared_args[name].shape == shape:
                arr = shared_args[name]
            else:
                if name in shared_args and not is_data:
                    # weight sharing requires shape invariance across
                    # buckets (reference shared_exec contract,
                    # graph_executor.cc Init shared-memory path): a
                    # silently re-allocated zero param would train/infer
                    # garbage for this bucket
                    raise MXNetError(
                        "shared param '%s' changes shape across buckets "
                        "(%s vs %s); bucketing shares weights, so every "
                        "bucket's symbol must give params the same shape"
                        % (name, shared_args[name].shape, shape))
                zeros = np.zeros(shape, dtype=np.float32)
                # params (and below, their grads) take their per-param
                # sharding — replicated on a dp mesh, fsdp-sharded under
                # the FSDP recipe; data/labels take the batch sharding
                arr = (self._place(zeros, baxis) if is_data
                       else self.place_param(name, zeros))
            args.append(arr)
            if self.grad_req.get(name, "null") != "null":
                if shared_group is not None and name in shared_group.executor.grad_dict:
                    g = shared_group.executor.grad_dict[name]
                    if g.shape == shape:
                        grads[name] = g
                        continue
                zeros = np.zeros(shape, dtype=np.float32)
                grads[name] = (self._place(zeros, baxis) if is_data
                               else self.place_param(name, zeros))

        aux = []
        shared_aux = {}
        if shared_group is not None:
            shared_aux = dict(zip(shared_group.aux_names,
                                  shared_group.executor.aux_arrays))
        # auxiliary states are float32 (BatchNorm's statistics) unless
        # their op says otherwise (a routed-expert layer's row counts)
        aux_types = self.symbol.infer_type()[2]
        for name, shape, dtype in zip(self.aux_names, aux_shapes, aux_types):
            if name in shared_aux and shared_aux[name].shape == shape:
                aux.append(shared_aux[name])
            else:
                aux.append(self._place(np.zeros(shape, dtype=dtype), None))

        self.executor = Executor(self.symbol, self.contexts[0], args,
                                 grads or None, self.grad_req, aux,
                                 label_names=self.label_names)
        self.execs = [self.executor]  # reference exposes per-device list

    def release_grad_buffers(self):
        """Move the bound gradient arrays off the accelerator: they stay
        valid zeros of the right shape on the host, where a reader or a
        later classic ``backward`` (which assigns fresh device arrays)
        finds them. For a step that computes its gradients inside one
        program and never writes them here."""
        import jax
        import jax.numpy as jnp

        host = jax.devices("cpu")[0]
        for g in self.executor.grad_arrays:
            if g is not None and host not in g._data.devices():
                g._data = jnp.zeros(g.shape, g._data.dtype, device=host)

    def _batch_axis_of(self, name: str) -> int:
        for d in self.data_shapes + self.label_shapes:
            if d.name == name:
                return DataDesc.get_batch_axis(d.layout)
        return 0

    # ------------------------------------------------------------------
    # parameter sync (reference set_params/get_params copy per device)
    # ------------------------------------------------------------------
    def set_params(self, arg_params: Dict[str, NDArray],
                   aux_params: Dict[str, NDArray]):
        def _placed_copy(arr, name=None):
            # _place is a no-copy when the source already lives on the
            # target device (device_put returns a fresh HANDLE to the SAME
            # buffer); the executor's buffers get DONATED (optimizer
            # update, fused-train-step aux), so they must never alias the
            # module-level host copies — donation would delete both
            import jax.numpy as jnp

            from ..ndarray import _shares_buffer

            placed = (self.place_param(name, arr) if name is not None
                      else self._place(arr, None))._data
            if isinstance(arr, NDArray) \
                    and _shares_buffer(placed, arr._data) is not False:
                # None (unverifiable aliasing) copies too — see
                # ndarray._shares_buffer
                placed = jnp.copy(placed)
            return placed

        for name, arr in arg_params.items():
            if name in self.executor.arg_dict:
                self.executor.arg_dict[name]._data = _placed_copy(arr,
                                                                  name)
        for name, arr in (aux_params or {}).items():
            if name in self.executor.aux_dict:
                bound = self.executor.aux_dict[name]
                bound._data = _placed_copy(arr).astype(bound.dtype)

    def get_params(self, arg_params: Dict[str, NDArray],
                   aux_params: Dict[str, NDArray]):
        for name in self.param_names:
            if name in self.executor.arg_dict:
                arg_params[name][:] = self.executor.arg_dict[name].asnumpy()
        for name, arr in zip(self.aux_names, self.executor.aux_arrays):
            if name in aux_params:
                aux_params[name][:] = arr.asnumpy()

    # ------------------------------------------------------------------
    # per-batch data loading (reference _load_data slice+copyto per dev;
    # here: one device_put with batch sharding)
    # ------------------------------------------------------------------
    def load_data_batch(self, data_batch):
        if getattr(data_batch, "aug", None) is not None:
            # device-feed batch reaching a classic (non-fused) consumer:
            # the raw uint8 frames don't fit the float crop-shaped data
            # buffer, so run the deferred augmentation eagerly first
            from ..io_cache import materialize_device_feed
            data_batch = materialize_device_feed(data_batch)
        for desc, arr in zip(self.data_shapes, data_batch.data):
            dst = self.executor.arg_dict[desc.name]
            baxis = DataDesc.get_batch_axis(desc.layout)
            dst._data = self._place(arr, baxis)._data
        self.load_label_batch(data_batch)

    def load_label_batch(self, data_batch):
        """Load ONLY the labels. The fused device-feed path uses this:
        raw uint8 frames bypass the executor's float data buffer (they
        ride the train jit's non-donated pack and are augmented
        in-graph), but labels still land in their arg slots."""
        if self.label_shapes:
            for desc, arr in zip(self.label_shapes, data_batch.label):
                dst = self.executor.arg_dict[desc.name]
                baxis = DataDesc.get_batch_axis(desc.layout)
                dst._data = self._place(arr, baxis)._data

    def forward(self, data_batch, is_train: Optional[bool] = None):
        self.load_data_batch(data_batch)
        if is_train is None:
            is_train = self.for_training
        self.executor.forward(is_train=is_train)

    def backward(self, out_grads=None):
        if not self.for_training:
            raise MXNetError("executor group bound for inference only")
        self.executor.backward(out_grads)

    def get_outputs(self) -> List[NDArray]:
        return self.executor.outputs

    def get_input_grads(self) -> List[NDArray]:
        if not self.inputs_need_grad:
            raise MXNetError("bound with inputs_need_grad=False")
        return [self.executor.grad_dict[n] for n in self.data_names]

    def update_metric(self, eval_metric, labels):
        eval_metric.update(labels, self.get_outputs())

    def install_monitor(self, mon):
        mon.install(self.executor)
