"""Kernels: device time of the operations whose HLO is a convolution or
a dot, alone or as the root of a fusion, per step (first device)."""


def read(trace, counters, spans, cell):
    return trace["conv_dot_s"] * 1e3 / trace["steps"]
