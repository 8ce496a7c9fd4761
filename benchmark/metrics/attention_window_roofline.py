"""Kernels: the windowed attention kernels' share of their roofline
(``scopes.part_roofline``): the band's USEFUL scores and weighted sums
(``sum_i min(i + 1, window)`` pairs a query head x 2 x 2 x head_dim,
``reference/laguna.py: layer_cost``), whatever blocks the kernels run, over
the device time under the windowed layers' ``CausalAttention``."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_roofline(trace, cell, "attention_window_kernel")
