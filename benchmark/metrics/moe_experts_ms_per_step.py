"""Kernels: device time a step under the expert layers: the norm,
``RoutedExperts`` (router, sort, grouped products, scatter) and the shared
expert."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("moe_grouped_matmul", "moe_rest"))
