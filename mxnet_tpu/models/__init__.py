"""Model zoo: symbol builders for the reference's example networks
(reference ``example/image-classification/symbol_*.py``, ``example/rnn``)."""
from .mlp import get_mlp
from .lenet import get_lenet
from .resnet import get_resnet, get_resnet50
from .inception_bn import get_inception_bn, get_inception_bn_28_small
from .lstm import lstm_unroll, lstm_fused
from .vision import (get_alexnet, get_vgg, get_googlenet,
                     get_inception_v3)
from .nemotron_h import get_nemotron_h
from .olmo_hybrid import get_olmo_hybrid
from .glm4_moe_lite import get_glm4_moe_lite
from .lfm2_moe import get_lfm2_moe
from .bailing_hybrid import get_bailing_hybrid

__all__ = ["get_mlp", "get_lenet", "get_resnet", "get_resnet50",
           "get_inception_bn", "get_inception_bn_28_small",
           "lstm_unroll", "lstm_fused", "get_alexnet", "get_vgg",
           "get_googlenet", "get_inception_v3", "get_nemotron_h",
           "get_olmo_hybrid", "get_glm4_moe_lite", "get_lfm2_moe",
           "get_bailing_hybrid"]
