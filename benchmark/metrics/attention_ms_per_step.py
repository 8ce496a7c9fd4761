"""Kernels: device time a step under the attention layer: the norm, the four
projections and ``CausalAttention``."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("attention_proj", "attention_kernel"))
