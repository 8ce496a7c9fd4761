"""Kernels: device time a step under the dense gated feed-forwards of all
blocks: the gate, up and down projections, the activation, the product,
and the block's norm and add after them."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("dense_ffn",))
