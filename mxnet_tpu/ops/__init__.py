"""Operator library: registry + op families.

Importing this package registers all built-in operators (the reference's
static registration via ``MXNET_REGISTER_OP_PROPERTY`` /
``MXNET_REGISTER_SIMPLE_OP``).
"""
from .registry import (Operator, OpContext, Param, REQUIRED, OP_REGISTRY,
                       register_op, create_operator)
from . import nn      # noqa: F401
from . import tensor  # noqa: F401
from . import seq     # noqa: F401
from . import vision  # noqa: F401
from . import ctc     # noqa: F401
from . import attention  # noqa: F401
from . import moe     # noqa: F401
# plugin ops that register symbols (caffe bridge); imported here so the
# creators exist before symbol-module generation
from ..plugins import caffe_op as _caffe_op  # noqa: F401,E402

__all__ = ["Operator", "OpContext", "Param", "REQUIRED", "OP_REGISTRY",
           "register_op", "create_operator"]
