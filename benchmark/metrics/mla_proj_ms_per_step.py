"""Kernels: device time a step under latent attention outside the kernel:
the low-rank query and key/value chains with the two latents' norms, the
slices, the rotary key's broadcast and the concatenation that build the
heads, the output projection, and the block's norm and add (part
``attention_proj`` of ``reference/glm4_moe_lite.py``; forward, recomputed
forward and backward together)."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("attention_proj",))
