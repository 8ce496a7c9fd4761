"""Kernels: device time a step under the rest of the state-space layers: the
norm, the two projections, the causal convolution with its activation and
the gated norm."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("ssm_proj_conv",))
