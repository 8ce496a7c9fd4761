"""Kernels: gated attention's projections' share of their roofline
(``scopes.part_roofline``; the count is the configuration's reference's,
``reference/qwen3_next.py: layer_cost``: 2 x 27.26 M x tokens a pass, each
weight, q with its gate, the normed q and k, v, the kernel's result and the
gated result once)."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_roofline(trace, cell, "attention_proj")
