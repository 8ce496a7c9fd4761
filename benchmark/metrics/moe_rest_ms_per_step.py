"""Kernels: device time a step under an expert layer outside ``RoutedExperts``:
the shared expert's three products, its scalar gate and the gate's product,
the sum with the routed part, and the block's norm and add (part ``moe_rest``
of the configuration's reference; forward, recomputed forward and backward
together). ``moe_experts_ms_per_step`` less the op itself."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("moe_rest",))
