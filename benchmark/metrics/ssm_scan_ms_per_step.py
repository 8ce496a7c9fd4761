"""Kernels: device time a step under the ``SSMScan`` nodes of the state-space
layers, by scope (``trace/scopes.py``)."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("ssm_scan",))
