"""Mesh + sharding-rule machinery.

Scaling recipe (the "pick a mesh, annotate shardings, let XLA insert
collectives" loop): build a Mesh over the device grid (ICI topology),
declare per-parameter PartitionSpecs via regex rules, place the batch
sharded along the data axes, and jit the train step — GSPMD partitions
the computation and emits the collectives.

The mesh is multi-axis by name: ``{"dp": N}`` is plain data
parallelism, ``{"dp": N, "fsdp": M}`` adds the FSDP recipe
(:func:`fsdp_param_spec`: params/opt-state sharded along ``fsdp``,
batch over ``dp x fsdp`` via :func:`batch_spec`), and ``{"dp": N,
"tp": K}`` the tensor-parallel serving recipe (:func:`tp_param_spec`:
each param sharded along ``tp`` on a per-param dim, batch over ``dp``
only — ``tp`` is a MODEL axis, not a data axis). The axis list stays
open for pp/ep recipes on the same abstraction.

Replaces (TPU-natively) the reference's explicit two-tier comm:
intra-node ``Comm`` reduce (``src/kvstore/comm.h``) and ps-lite push/pull
(``src/kvstore/kvstore_dist.h``).
"""
from __future__ import annotations

import re
from collections import namedtuple
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..base import MXNetError

__all__ = ["make_mesh", "make_param_shardings", "shard_args",
           "build_sgd_train_step", "ShardingRule", "mesh_axis_sizes",
           "batch_spec", "fsdp_param_spec", "tp_param_spec",
           "batch_shard_extent", "DATA_AXES"]

ShardingRule = namedtuple("ShardingRule", ["pattern", "spec"])

#: Mesh axes the BATCH shards over, in mesh-major order. ``dp`` is pure
#: data parallelism (params replicated across it); ``fsdp`` also shards
#: the batch — its distinguishing role is sharding params/opt-state.
#: Future recipe axes (tp/pp/ep) are NOT batch axes and join the mesh
#: without extending this tuple.
DATA_AXES = ("dp", "fsdp")


def make_mesh(axis_sizes: Dict[str, int], devices: Optional[Sequence] = None):
    """Create a Mesh with named axes, e.g. {'dp': 4, 'tp': 2}."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    sizes = list(axis_sizes.values())
    n = int(np.prod(sizes))
    if len(devices) < n:
        raise MXNetError("mesh needs %d devices, have %d" % (n, len(devices)))
    grid = np.array(devices[:n]).reshape(sizes)
    return Mesh(grid, tuple(axis_sizes.keys()))


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    """``{axis_name: size}`` of a Mesh, in axis order — the snapshot
    form checkpoint.py records so a resume can log exactly which mesh
    shape the state is re-sharding from/onto."""
    return {name: int(mesh.shape[name]) for name in mesh.axis_names}


def batch_spec(mesh, batch_axis: int):
    """PartitionSpec sharding ``batch_axis`` over every data axis the
    mesh carries (``dp``, and ``fsdp`` when present): the global batch
    splits across ALL devices regardless of how the grid is factored
    between replication and param sharding."""
    from jax.sharding import PartitionSpec as P

    axes = tuple(a for a in DATA_AXES if a in mesh.axis_names)
    spec = [None] * (batch_axis + 1)
    spec[batch_axis] = axes if len(axes) > 1 else axes[0]
    return P(*spec)


def fsdp_param_spec(shape, mesh, axis: str = "fsdp"):
    """PartitionSpec for a param/opt-state array under the FSDP recipe:
    dim 0 sharded along ``axis`` when it divides evenly (the ZeRO-style
    1-D shard), fully replicated otherwise (odd-shaped leaves — e.g. a
    bias whose length does not divide — cost little replicated, and a
    ragged shard would force padding collectives). Returns None when
    the mesh has no ``axis``."""
    from jax.sharding import PartitionSpec as P

    if axis not in getattr(mesh, "axis_names", ()):
        return None
    size = int(mesh.shape[axis])
    if size <= 1 or not shape or shape[0] % size != 0:
        return P()
    # no trailing Nones: P(axis) is the spelling XLA hands back on a
    # step's outputs, and jit keys its cache on the spelling — a param
    # that enters as P(axis, None) and returns as P(axis) recompiles
    # the fused step once, on the second batch
    return P(axis)


def batch_shard_extent(mesh) -> int:
    """How many ways the batch axis shards on this mesh: the product of
    the DATA axes present (``dp``, ``dp x fsdp``) — NOT ``mesh.size``.
    On a ``(dp, tp)`` mesh the batch shards ``dp`` ways while ``tp``
    splits the model, so rounding batch rungs to ``mesh.size`` would
    over-pad every bucket. 1 for no mesh."""
    if mesh is None:
        return 1
    extent = 1
    for a in DATA_AXES:
        if a in mesh.axis_names:
            extent *= int(mesh.shape[a])
    return extent


def tp_param_spec(shape, mesh, axis: str = "tp"):
    """PartitionSpec for a param under the tensor-parallel serving
    recipe: the LARGEST dim that divides evenly by the ``axis`` size is
    sharded along it (ties go to the earliest dim — for an FC weight
    ``(out, in)`` that is the Megatron-style column split), fully
    replicated when no dim divides (odd-shaped leaves cost little
    replicated, and a ragged shard would force padding collectives).
    Returns None when the mesh has no ``axis``."""
    from jax.sharding import PartitionSpec as P

    if axis not in getattr(mesh, "axis_names", ()):
        return None
    size = int(mesh.shape[axis])
    if size <= 1 or not shape:
        return P()
    best = None
    for d, dim in enumerate(shape):
        if dim % size == 0 and (best is None or dim > shape[best]):
            best = d
    if best is None:
        return P()
    spec = [None] * len(shape)
    spec[best] = axis
    return P(*spec)


def _spec_fits(shape, spec, mesh) -> bool:
    """A PartitionSpec only applies if every sharded dim divides evenly."""
    for dim, axis in zip(shape, tuple(spec)):
        if axis is None:
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        if dim % size != 0:
            return False
    return True


def make_param_shardings(mesh, name_to_shape: Dict[str, tuple],
                         rules: Sequence[ShardingRule]):
    """name -> NamedSharding from the first matching rule whose spec divides
    the shape; unmatched / non-dividing params replicate."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = {}
    for name, shape in name_to_shape.items():
        sharding = NamedSharding(mesh, P())
        for rule in rules:
            if re.match(rule.pattern, name) and _spec_fits(shape, rule.spec, mesh):
                sharding = NamedSharding(mesh, rule.spec)
                break
        out[name] = sharding
    return out


def shard_args(mesh, arrays: Dict[str, np.ndarray], shardings: Dict):
    """device_put each named array with its sharding."""
    import jax

    return {name: jax.device_put(arr, shardings[name])
            for name, arr in arrays.items()}


def build_sgd_train_step(symbol, data_names: Sequence[str],
                         label_names: Sequence[str], lr: float = 0.01,
                         compute_dtype=None):
    """Return ``step(params, data, aux, key) -> (outputs, new_params,
    new_aux)`` — forward, backward (jax.vjp through the whole graph) and
    SGD update fused into ONE jittable computation. Under a mesh with
    sharded inputs, XLA inserts the gradient all-reduce (dp) and the
    matmul collectives (tp) automatically.

    ``compute_dtype`` (e.g. ``jnp.bfloat16``) enables mixed precision:
    params and data are cast on entry (labels never are), activations and
    matmuls run in that dtype on the MXU, while master weights, the SGD
    update, and BatchNorm statistics stay float32. The vjp of the cast
    returns float32 gradients automatically."""
    import jax
    import jax.numpy as jnp

    from ..base import getenv
    from ..executor import make_graph_eval

    # MXNET_BACKWARD_DO_MIRROR (reference memonger mirroring): segmented
    # remat inside the graph eval — see make_graph_eval(remat=True)
    eval_graph, n_aux = make_graph_eval(
        symbol, remat=getenv("MXNET_BACKWARD_DO_MIRROR", False))
    arg_names = symbol.list_arguments()
    label_set = set(label_names)
    input_names = set(data_names) | label_set
    param_names = [n for n in arg_names if n not in input_names]

    def _cast(x):
        if compute_dtype is not None and jnp.issubdtype(x.dtype,
                                                        jnp.floating):
            return x.astype(compute_dtype)
        return x

    def step(params: Dict, data: Dict, aux: List, key):
        def f(params):
            args = []
            for n in arg_names:
                if n in params:
                    args.append(_cast(params[n]))
                elif n in label_set:
                    args.append(data[n])  # labels keep full precision
                else:
                    args.append(_cast(data[n]))
            outputs, aux_out = eval_graph(args, aux, key, True)
            return outputs, aux_out

        (outputs, aux_out), vjp = jax.vjp(f, params)
        heads = [jnp.ones_like(o) for o in outputs]
        zero_aux = [jnp.zeros_like(a) for a in aux_out]
        grads, = vjp((heads, zero_aux))
        new_params = {n: params[n] - lr * grads[n] for n in params}
        aux_out = [a.astype(b.dtype) for a, b in zip(aux_out, aux)]
        return outputs, new_params, aux_out

    return step, param_names
