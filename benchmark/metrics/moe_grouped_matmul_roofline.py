"""Kernels: the routed experts' share of their roofline: the grouped products
for the rows really routed (``moe.rows_here``) over the device time under
``RoutedExperts``, router and sort included."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_roofline(trace, cell, "moe_grouped_matmul")
