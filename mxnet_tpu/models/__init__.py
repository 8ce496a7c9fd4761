"""Model zoo: symbol builders for the reference's example networks
(reference ``example/image-classification/symbol_*.py``, ``example/rnn``),
and beyond it the language models the benchmark trains through
``Module.fit`` at their published widths: ``get_nemotron_h`` (Mamba-2 scans,
routed experts, grouped-head attention), ``get_olmo_hybrid`` (the gated
delta rule, QK-normed attention, dense SwiGLU), ``get_glm4_moe_lite``
(latent attention, gated routed experts), ``get_lfm2_moe`` (gated short
convolutions, 64-wide heads, a tied head), ``get_bailing_hybrid`` (the delta
rule with a decay a key channel, group-limited sigmoid routing),
``get_qwen3_next`` (the delta rule with two value heads a key head,
attention with an output gate and partial rotary, softmax-routed experts
with an auxiliary load-balancing loss beside a gated shared expert) and
``get_laguna`` (sliding-window and full attention mixed three to one, more
query heads on the windowed layers, a gate a head, rotary scaled by length
on the full layers, 256 small experts beside a shared one)."""
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
from .qwen3_next import get_qwen3_next
from .laguna import get_laguna

__all__ = ["get_mlp", "get_lenet", "get_resnet", "get_resnet50",
           "get_inception_bn", "get_inception_bn_28_small",
           "lstm_unroll", "lstm_fused", "get_alexnet", "get_vgg",
           "get_googlenet", "get_inception_v3", "get_nemotron_h",
           "get_olmo_hybrid", "get_glm4_moe_lite", "get_lfm2_moe",
           "get_bailing_hybrid", "get_qwen3_next", "get_laguna"]
