"""Evaluation metrics (reference ``python/mxnet/metric.py``).

Device-side accumulation: metrics whose math is expressible as a pure
per-batch fold (``has_device_fold``) keep a running ``(sum, count)``
pair ON DEVICE and only fetch it to the host in :meth:`EvalMetric.get`
(Speedometer / epoch-report cadence). The reference synced every batch:
each ``update`` called ``asnumpy``, serializing the dispatch queue. Here
``update`` dispatches one small async fold instead, and the fused train
step (:mod:`mxnet_tpu.fused_step`) folds the same math INTO the training
computation so a batch costs zero extra dispatches.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Union

import numpy as np

from . import telemetry as _tel
from .base import MXNetError, Registry
from .ndarray import NDArray

__all__ = ["EvalMetric", "Accuracy", "TopKAccuracy", "F1", "MAE", "MSE",
           "RMSE", "CrossEntropy", "CompositeEvalMetric", "CustomMetric",
           "np_metric", "create"]

_REG: Registry = Registry.get_registry("metric")

# jitted device folds shared across metric instances, keyed by
# (_fold_cache_key(), n_pairs): metrics are constructed per fit()/score()
# call, and a per-instance jit would recompile the same tiny fold for
# every one of them
_FOLD_FNS: dict = {}


def _replicated_zero(like):
    """A zero f32 scalar placed compatibly with ``like``: replicated over
    ``like``'s device set so a jit mixing the accumulator with sharded
    batch outputs (multi-device executor) sees one consistent mesh."""
    import jax
    import jax.numpy as jnp

    z = jnp.zeros((), jnp.float32)
    sharding = getattr(like, "sharding", None)
    if sharding is None:
        return z
    try:
        from jax.sharding import NamedSharding, PartitionSpec

        if isinstance(sharding, NamedSharding):
            return jax.device_put(
                z, NamedSharding(sharding.mesh, PartitionSpec()))
        devs = list(sharding.device_set)
        if len(devs) == 1:
            return jax.device_put(z, devs[0])
    except Exception:
        pass
    return z


def _device_set(x):
    """frozenset of the devices ``x`` is committed to, or None when it
    carries no sharding (uncommitted / not a jax array). Devices, not
    their ids: a host with a TPU has a ``cpu:0`` AND a ``tpu:0``, and
    labels from a host iterator must not look colocated with
    predictions on the chip."""
    sharding = getattr(x, "sharding", None)
    if sharding is None:
        return None
    return frozenset(sharding.device_set)


def check_label_shapes(labels, preds):
    if len(labels) != len(preds):
        raise MXNetError("labels/preds count mismatch: %d vs %d"
                         % (len(labels), len(preds)))


class EvalMetric:
    # True on subclasses that implement device_fold; such metrics keep a
    # cumulative (sum, count) pair on device (self._device_acc) and read
    # it back only in get()
    has_device_fold = False

    def __init__(self, name: str, num: Optional[int] = None):
        self.name = name
        self.num = num
        self.reset()

    def reset(self):
        if self.num is None:
            self.num_inst = 0
            self.sum_metric = 0.0
        else:
            self.num_inst = [0] * self.num
            self.sum_metric = [0.0] * self.num
        self._device_acc = None
        self._fold_fn = None

    def device_fold(self, label, pred):
        """Pure jnp fold of ONE (label, pred) pair into ``(sum_delta,
        count_delta)`` f32 scalars — the jit-friendly form of this
        metric's update math. Traceable inside the fused train step."""
        raise NotImplementedError

    def _fold_cache_key(self):
        """Key under which this metric's jitted fold may be shared with
        other instances; subclasses whose device_fold reads instance
        config (top_k, eps, ...) must extend it."""
        return (type(self),)

    def _lazy_update(self, labels, preds) -> bool:
        """Accumulate this batch on device without any host sync; True
        when handled (the numpy path must then be skipped). Only for
        scalar (num is None) metrics with a device fold over NDArray
        inputs — anything else falls through to the eager path."""
        if not self.has_device_fold or self.num is not None:
            return False
        labels, preds = list(labels), list(preds)
        if not labels or len(labels) != len(preds):
            return False
        if not all(isinstance(a, NDArray) for a in labels + preds):
            return False
        # one jit needs one consistent device set: a multi-device
        # executor shards preds over the mesh while labels sit on one
        # device — that batch takes the eager numpy path instead
        # (get() still folds in whatever the accumulator already holds)
        sets = {_device_set(a._data) for a in labels + preds}
        sets.discard(None)
        if len(sets) > 1:
            return False
        if self._device_acc is not None and sets \
                and _device_set(self._device_acc[0]) not in (
                    None, next(iter(sets))):
            return False
        import jax

        if self._fold_fn is None:
            key = self._fold_cache_key()
            fn = _FOLD_FNS.get(key)
            if fn is None:
                fold = self.device_fold

                def accum(acc, labs, ps):
                    s, c = acc
                    for lab, p in zip(labs, ps):
                        ds, dc = fold(lab, p)
                        s = s + ds
                        c = c + dc
                    return s, c

                from . import xprof as _xprof

                _FOLD_FNS[key] = fn = _xprof.jit(
                    accum, site="metric.fold",
                    arg_names=("acc", "labels", "preds"))
            self._fold_fn = fn
        acc = self._device_acc
        if acc is None:
            from .analysis import sanitizers as _san

            with _san.intentional_transfer():
                z = _replicated_zero(preds[0]._data)
            acc = (z, z)
        _tel.inc("step.dispatches")
        self._device_acc = self._fold_fn(
            acc, [a._data for a in labels], [p._data for p in preds])
        return True

    def _host_totals(self):
        """(sum, count) with the device accumulator folded in — the ONLY
        place the accumulator syncs to the host."""
        from .analysis import sanitizers as _san

        s, n = self.sum_metric, self.num_inst
        if self._device_acc is not None:
            acc_s, acc_c = self._device_acc
            with _san.intentional_transfer():
                s = s + float(acc_s)  # graft: host-sync
                n = n + float(acc_c)  # graft: host-sync
        return s, n

    def update(self, labels: Sequence[NDArray], preds: Sequence[NDArray]):
        raise NotImplementedError

    def get(self):
        if self.num is None:
            s, n = self._host_totals()
            value = s / n if n else float("nan")
            return self.name, value
        names = ["%s_%d" % (self.name, i) for i in range(self.num)]
        values = [s / n if n else float("nan")
                  for s, n in zip(self.sum_metric, self.num_inst)]
        return names, values

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            return [(name, value)]
        return list(zip(name, value))


@_REG.register("acc")
@_REG.register("accuracy")
class Accuracy(EvalMetric):
    has_device_fold = True

    def __init__(self):
        super().__init__("accuracy")

    def device_fold(self, label, pred):
        import jax.numpy as jnp

        lab = label.astype(jnp.int32).ravel()
        pl = jnp.argmax(pred, axis=1) if pred.ndim > 1 else pred
        hits = (pl.astype(jnp.int32).ravel() == lab).sum()
        return hits.astype(jnp.float32), jnp.float32(lab.size)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        if self._lazy_update(labels, preds):
            return
        for label, pred in zip(labels, preds):
            p = pred.asnumpy()  # graft: host-sync
            pred_label = np.argmax(p, axis=1) if p.ndim > 1 else p
            lab = label.asnumpy().astype(np.int32).ravel()  # graft: host-sync
            self.sum_metric += int((pred_label.astype(np.int32).ravel() == lab).sum())
            self.num_inst += len(lab)


@_REG.register("top_k_accuracy")
class TopKAccuracy(EvalMetric):
    def __init__(self, top_k: int = 1, **kwargs):
        self.top_k = kwargs.get("top_k", top_k)
        super().__init__("top_k_accuracy_%d" % self.top_k)
        if self.top_k <= 1:
            raise MXNetError("top_k should be >1; use Accuracy otherwise")

    has_device_fold = True

    def _fold_cache_key(self):
        return (type(self), self.top_k)

    def device_fold(self, label, pred):
        import jax.numpy as jnp

        lab = label.astype(jnp.int32).ravel()
        topk = jnp.argsort(pred, axis=1)[:, -self.top_k:]
        hits = (topk == lab[:, None]).any(axis=1).sum()
        return hits.astype(jnp.float32), jnp.float32(lab.size)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        if self._lazy_update(labels, preds):
            return
        for label, pred in zip(labels, preds):
            p = pred.asnumpy().astype(np.float32)  # graft: host-sync
            lab = label.asnumpy().astype(np.int32)  # graft: host-sync
            topk = np.argsort(p, axis=1)[:, -self.top_k:]
            for i in range(len(lab)):
                self.sum_metric += int(lab[i] in topk[i])
            self.num_inst += len(lab)


@_REG.register("f1")
class F1(EvalMetric):
    """Binary F1 (reference metric.py F1)."""

    def __init__(self):
        super().__init__("f1")

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            p = np.argmax(pred.asnumpy(), axis=1)  # graft: host-sync
            lab = label.asnumpy().astype(np.int32).ravel()  # graft: host-sync
            if len(np.unique(lab)) > 2:
                raise MXNetError("F1 supports binary classification only")
            tp = int(((p == 1) & (lab == 1)).sum())
            fp = int(((p == 1) & (lab == 0)).sum())
            fn = int(((p == 0) & (lab == 1)).sum())
            precision = tp / (tp + fp) if tp + fp else 0.0
            recall = tp / (tp + fn) if tp + fn else 0.0
            f1 = 2 * precision * recall / (precision + recall) \
                if precision + recall else 0.0
            self.sum_metric += f1
            self.num_inst += 1


@_REG.register("mae")
class MAE(EvalMetric):
    has_device_fold = True

    def __init__(self):
        super().__init__("mae")

    def device_fold(self, label, pred):
        import jax.numpy as jnp

        err = jnp.abs(label - pred.reshape(label.shape)).mean()
        return err.astype(jnp.float32), jnp.float32(1.0)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        if self._lazy_update(labels, preds):
            return
        for label, pred in zip(labels, preds):
            l_np = label.asnumpy()  # graft: host-sync
            p_np = pred.asnumpy().reshape(l_np.shape)  # graft: host-sync
            self.sum_metric += float(np.abs(l_np - p_np).mean())
            self.num_inst += 1


@_REG.register("mse")
class MSE(EvalMetric):
    has_device_fold = True

    def __init__(self):
        super().__init__("mse")

    def device_fold(self, label, pred):
        import jax.numpy as jnp

        err = ((label - pred.reshape(label.shape)) ** 2).mean()
        return err.astype(jnp.float32), jnp.float32(1.0)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        if self._lazy_update(labels, preds):
            return
        for label, pred in zip(labels, preds):
            l_np = label.asnumpy()  # graft: host-sync
            p_np = pred.asnumpy().reshape(l_np.shape)  # graft: host-sync
            self.sum_metric += float(((l_np - p_np) ** 2).mean())
            self.num_inst += 1


@_REG.register("rmse")
class RMSE(EvalMetric):
    has_device_fold = True

    def __init__(self):
        super().__init__("rmse")

    def device_fold(self, label, pred):
        import jax.numpy as jnp

        err = jnp.sqrt(((label - pred.reshape(label.shape)) ** 2).mean())
        return err.astype(jnp.float32), jnp.float32(1.0)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        if self._lazy_update(labels, preds):
            return
        for label, pred in zip(labels, preds):
            l_np = label.asnumpy()  # graft: host-sync
            p_np = pred.asnumpy().reshape(l_np.shape)  # graft: host-sync
            self.sum_metric += float(np.sqrt(((l_np - p_np) ** 2).mean()))
            self.num_inst += 1


@_REG.register("ce")
@_REG.register("cross-entropy")
class CrossEntropy(EvalMetric):
    has_device_fold = True

    def __init__(self, eps: float = 1e-8):
        super().__init__("cross-entropy")
        self.eps = eps

    def _fold_cache_key(self):
        return (type(self), self.eps)

    def device_fold(self, label, pred):
        import jax.numpy as jnp

        lab = label.astype(jnp.int32).ravel()
        prob = jnp.take_along_axis(pred, lab[:, None], axis=1)[:, 0]
        loss = (-jnp.log(prob + self.eps)).sum()
        return loss.astype(jnp.float32), jnp.float32(lab.size)

    def update(self, labels, preds):
        check_label_shapes(labels, preds)
        if self._lazy_update(labels, preds):
            return
        for label, pred in zip(labels, preds):
            lab = label.asnumpy().astype(np.int32).ravel()  # graft: host-sync
            p = pred.asnumpy()  # graft: host-sync
            prob = p[np.arange(lab.shape[0]), lab]
            self.sum_metric += float((-np.log(prob + self.eps)).sum())
            self.num_inst += len(lab)


class CompositeEvalMetric(EvalMetric):
    def __init__(self, metrics: Optional[List[EvalMetric]] = None, **kwargs):
        super().__init__("composite")
        self.metrics = metrics or []

    def add(self, metric: "EvalMetric"):
        self.metrics.append(metric)

    def get_metric(self, index: int) -> EvalMetric:
        return self.metrics[index]

    def reset(self):
        for m in getattr(self, "metrics", []):
            m.reset()

    def update(self, labels, preds):
        for m in self.metrics:
            m.update(labels, preds)

    def get(self):
        names, values = [], []
        for m in self.metrics:
            n, v = m.get()
            names.extend(n if isinstance(n, list) else [n])
            values.extend(v if isinstance(v, list) else [v])
        return names, values


class CustomMetric(EvalMetric):
    """Wrap ``feval(label, pred) -> float`` (reference CustomMetric)."""

    def __init__(self, feval: Callable, name: Optional[str] = None,
                 allow_extra_outputs: bool = False):
        name = name or getattr(feval, "__name__", "custom")
        super().__init__("custom(%s)" % name)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            check_label_shapes(labels, preds)
        for label, pred in zip(labels, preds):
            # graft: host-sync
            reval = self._feval(label.asnumpy(), pred.asnumpy())
            if isinstance(reval, tuple):
                sum_metric, num_inst = reval
                self.sum_metric += sum_metric
                self.num_inst += num_inst
            else:
                self.sum_metric += reval
                self.num_inst += 1


def np_metric(numpy_feval: Callable, name: Optional[str] = None,
              allow_extra_outputs: bool = False):
    """Decorator creating a CustomMetric from a numpy function."""
    def feval(label, pred):
        return numpy_feval(label, pred)
    feval.__name__ = name or numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


def create(metric: Union[str, Callable, EvalMetric], **kwargs) -> EvalMetric:
    if isinstance(metric, EvalMetric):
        return metric
    if callable(metric):
        return CustomMetric(metric)
    if isinstance(metric, list):
        composite = CompositeEvalMetric()
        for m in metric:
            composite.add(create(m, **kwargs))
        return composite
    cls = _REG.get(metric)
    return cls(**kwargs)
