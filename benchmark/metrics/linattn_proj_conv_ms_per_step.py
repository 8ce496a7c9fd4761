"""Kernels: device time a step under the rest of the linear-attention
mixers: the six projections (query, key, value, the two per-head gates, the
output gate), the three causal convolutions with their activations, the
gated norm, the output projection and the block's norm and add after it."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("linattn_proj_conv",))
