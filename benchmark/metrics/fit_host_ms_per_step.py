"""Fit loop: the program's own Python per step inside the traced
stretch: its ``fit.step`` spans less the ``fit.next`` (the iterator) and
``step.dispatch`` (the call of the jitted step, which blocks when the
runtime's queue is full) inside them. What sets the ceiling once the
device is faster."""
from benchmark.trace import program_spans


def read(trace, counters, spans, cell):
    lo, hi = program_spans.stretch(spans)
    entries = program_spans.ring("fit_host_ms_per_step", since=lo)
    if entries is None or not counters["steps"]:
        return None
    own = program_spans.covered(entries, ("fit.step",), lo, hi,
                                less=("fit.next", "step.dispatch"))
    return own * 1e3 / counters["steps"]
