"""Kernels: the attention kernel's share of its roofline: the causal half of
the scores and of the weighted sum over the device time under
``CausalAttention``, rotation included."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_roofline(trace, cell, "attention_kernel")
