"""Operator registry and base class.

TPU-native re-design of the reference's operator interface
(``include/mxnet/operator.h:165-485`` ``OperatorProperty``): each operator
declares its arguments/outputs/auxiliary states, shape+type inference, and a
pure ``apply`` function over jnp arrays. Gradients come from jax autodiff
through ``apply``; ops whose reference gradient differs from the
mathematical one (SoftmaxOutput, MakeLoss, BlockGrad, regression outputs)
implement it with ``jax.custom_vjp`` inside ``apply``.

Registration (reference ``MXNET_REGISTER_OP_PROPERTY``,
``operator.h:537``) also auto-generates the symbol creation function, like
the reference's C-registry-driven codegen
(``python/mxnet/symbol.py`` ``_init_symbol_module``).

Parameter declaration mirrors ``dmlc::Parameter``/``DMLC_DECLARE_PARAMETER``:
a ``PARAMS`` dict of :class:`Param` specs with type/default/doc, parsed and
validated at symbol creation and round-tripped through JSON serialization.
"""
from __future__ import annotations

import ast
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..base import MXNetError, Registry

__all__ = ["Param", "REQUIRED", "Operator", "OpContext", "register_op",
           "OP_REGISTRY", "create_operator"]

OP_REGISTRY: Registry = Registry.get_registry("operator")

REQUIRED = object()


class Param:
    """One declared parameter (``DMLC_DECLARE_PARAMETER`` field)."""

    def __init__(self, ptype, default=REQUIRED, doc=""):
        self.ptype = ptype      # int/float/bool/str/'shape'
        self.default = default
        self.doc = doc

    def parse(self, value):
        if value is None:
            return None
        if self.ptype == "shape":
            if isinstance(value, str):
                value = ast.literal_eval(value)
            if isinstance(value, int):
                value = (value,)
            return tuple(int(v) for v in value)
        if self.ptype is bool:
            if isinstance(value, str):
                return value.lower() in ("1", "true", "yes")
            return bool(value)
        if self.ptype is int and isinstance(value, str):
            return int(value)
        if self.ptype is float and isinstance(value, str):
            return float(value)
        return self.ptype(value)


class OpContext:
    """Per-invocation context handed to ``apply`` (reference ``OpContext``,
    ``operator.h:44-62``): training mode flag and a PRNG key (the reference's
    per-device ``Random<xpu>`` resource, ``include/mxnet/resource.h``).

    ``kept`` is what the segmented recomputation (``executor.
    make_graph_eval(remat=True)``) keeps of this node: ``{result: name}``
    for the results of :meth:`Operator.remat_results` it chose, empty
    everywhere else."""

    __slots__ = ("is_train", "rng", "kept")

    def __init__(self, is_train: bool, rng=None, kept=None):
        self.is_train = is_train
        self.rng = rng
        self.kept = kept or {}

    def keep(self, value, result: str):
        """``value`` (an array or a tree of them) marked as kept where the
        recomputation plan chose ``result`` of this node, else as it is:
        outside recomputation nothing is added to the program."""
        name = self.kept.get(result)
        if name is None:
            return value
        import jax
        from jax.ad_checkpoint import checkpoint_name

        return jax.tree_util.tree_map(
            lambda v: checkpoint_name(v, name), value)


class Operator:
    """Base class: one instance per graph node, holding parsed params."""

    # subclasses override
    PARAMS: Dict[str, Param] = {}
    name_hint = "op"
    # names of arguments that must reach ``apply`` in their own dtype
    # under mixed precision (MXNET_COMPUTE_DTYPE): integer ids that a
    # float cast would corrupt, float32 quantities a rounding would move
    # discontinuously. The executor's cast rule skips the variables that
    # feed them.
    full_precision_args: Sequence[str] = ()

    def __init__(self, **kwargs):
        unknown = [k for k in kwargs if k not in self.PARAMS]
        if unknown:
            # report typos before "missing required" — a misspelled kwarg
            # otherwise surfaces as a confusing missing-parameter error
            raise MXNetError("%s: unknown parameters %s (known: %s)" % (
                type(self).__name__, sorted(unknown), sorted(self.PARAMS)))
        params = {}
        for key, spec in self.PARAMS.items():
            if key in kwargs:
                params[key] = spec.parse(kwargs.pop(key))
            elif spec.default is REQUIRED:
                raise MXNetError("%s: required parameter '%s' missing"
                                 % (type(self).__name__, key))
            else:
                params[key] = spec.default
        self.params = params

    def __getattr__(self, item):
        try:
            return self.__dict__["params"][item]
        except KeyError:
            raise AttributeError(item)

    # -- interface ---------------------------------------------------------
    def list_arguments(self) -> List[str]:
        return ["data"]

    def list_outputs(self) -> List[str]:
        return ["output"]

    def list_auxiliary_states(self) -> List[str]:
        return []

    @property
    def num_outputs(self) -> int:
        return len(self.list_outputs())

    def infer_shape(self, in_shapes: List[Optional[Tuple[int, ...]]]):
        """Returns (in_shapes, out_shapes, aux_shapes); must fill unknowns or
        raise (reference ``OperatorProperty::InferShape``)."""
        shape = _first_known(in_shapes)
        if shape is None:
            raise MXNetError("%s: cannot infer shape" % type(self).__name__)
        return [shape] * len(in_shapes), [shape], []

    def infer_type(self, in_types, out_types=None):
        """Default same-dtype rule: inputs and outputs all share the first
        known dtype, looking at BOTH sides so the symbol-level fixpoint can
        propagate backward (reference ``InferNodeTypes`` iterates nodes in
        both directions). Returns None-filled lists when nothing is known —
        never speculate; the symbol-level pass defaults leftover variables
        to float32 afterwards."""
        import numpy as np

        known = list(in_types) + list(out_types or [])
        dtype = next((t for t in known if t is not None), None)
        if dtype is None:
            return (list(in_types), [None] * self.num_outputs,
                    [np.float32] * len(self.list_auxiliary_states()))
        return ([dtype] * len(in_types), [dtype] * self.num_outputs,
                [np.float32] * len(self.list_auxiliary_states()))

    def remat_results(self, in_shapes, in_types):
        """What the segmented recomputation (``MXNET_BACKWARD_DO_MIRROR``,
        ``executor.make_graph_eval``) may keep of this node instead of
        computing it again: ``[(result, bytes held, operations)]`` from
        the input shapes and dtypes. ``result`` is the label under which
        ``apply`` hands the value to ``ctx.keep``; ``operations`` is what
        computing it again costs, and the plan keeps by descending
        operations a byte as far as the device's memory allows.
        ``operations`` ``None``: dear to compute again and cheap to hold
        whatever the shapes, so kept always. Everything an op does not
        list is recomputed inside its segment."""
        return []

    def weight_grad_flops(self, in_shapes):
        """``{input slot: operations}`` of the products that form the
        gradient of each input this node reads as a weight, from the input
        shapes: what the fused step holds against the optimizer's bytes
        when it places the seam between a parameter's weight-gradient
        product and its update (``fused_step._plan_update_seam``). A slot
        an op does not list states nothing, and its parameter's update
        rides whatever produces the gradient."""
        return {}

    def apply(self, ctx: OpContext, inputs: Sequence[Any], aux: Sequence[Any]):
        """Pure function over jnp arrays -> (outputs, new_aux)."""
        raise NotImplementedError

    # serialization helpers
    def param_str_dict(self) -> Dict[str, str]:
        return {k: str(v) for k, v in self.params.items() if v is not None}


def _first_known(shapes):
    for s in shapes:
        if s is not None:
            return s
    return None


def register_op(name: str, aliases: Sequence[str] = ()):
    """Register an Operator subclass under ``name`` (+ aliases)."""

    def _do(cls):
        cls.op_name = name
        cls.op_aliases = tuple(aliases)
        OP_REGISTRY.register(name)(cls)
        for alias in aliases:
            # the registry keys case-insensitively, so an alias that only
            # differs in case (e.g. "crop" for "Crop") already resolves —
            # it still matters for namespace exposure via op_aliases
            if OP_REGISTRY.find(alias) is cls:
                continue
            OP_REGISTRY.register(alias)(cls)
        return cls
    return _do


def create_operator(op_name: str, **params) -> Operator:
    cls = OP_REGISTRY.get(op_name)
    return cls(**params)


def get_operator_class(op_name: str):
    """Registered Operator class, or None if unknown (no raise)."""
    return OP_REGISTRY.find(op_name)


def same_shape_binary(in_shapes):
    """Shape rule for elementwise binary ops: both inputs same shape."""
    known = _first_known(in_shapes)
    if known is None:
        raise MXNetError("cannot infer shape of elementwise op")
    filled = [s if s is not None else known for s in in_shapes]
    for s in filled:
        if s != known:
            raise MXNetError("elementwise op shape mismatch: %s" % (filled,))
    return filled, [known], []
