"""Kernels: device time a step under the ``GatedDeltaRule`` nodes of the
linear-attention layers, by scope (``trace/scopes.py``): the chunked delta
rule's forward, recomputed forward and backward passes."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("linattn_scan",))
