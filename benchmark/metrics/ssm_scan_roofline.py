"""Kernels: the chunked state-space scan's share of its roofline
(``scopes.part_roofline``; the count is ``reference/nemotron_h.py``'s)."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_roofline(trace, cell, "ssm_scan")
