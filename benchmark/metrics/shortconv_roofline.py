"""Kernels: the gated short-convolution mixers' share of their roofline
(``scopes.part_roofline``; the count is the configuration's reference's,
``reference/lfm2_moe.py: layer_cost``: 2 x 16.78 M x tokens a pass for the
two projections and the taps and 2 x 2,048 x tokens for the gates, each
weight, the in-projection's result and the operator's once)."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_roofline(trace, cell, "shortconv")
