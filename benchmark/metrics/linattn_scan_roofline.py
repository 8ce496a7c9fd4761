"""Kernels: the chunked gated delta rule's share of its roofline
(``scopes.part_roofline``; the count is the configuration's reference's,
``reference/olmo_hybrid.py: layer_cost``)."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_roofline(trace, cell, "linattn_scan")
