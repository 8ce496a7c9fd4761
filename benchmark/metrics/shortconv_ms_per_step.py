"""Kernels: device time a step under the gated short-convolution mixers:
the block's norm, ``W_in``, the two gates and the depthwise causal
convolution between them (``GatedShortConv``), ``W_out`` and the block's
add (part ``shortconv`` of ``reference/lfm2_moe.py``; forward, recomputed
forward and backward together)."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("shortconv",))
