"""Kernels: device time a step under the final norm, the head over the
vocabulary, ``SoftmaxOutput`` and the metric's fold over the probabilities."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("lm_head_loss",))
