"""Fit loop: 95th percentile of the program's ``fit.step`` spans (one
loop iteration of ``Module.fit``, boundary to boundary, on the host's
clock) that lie wholly inside the traced stretch."""
import math

from benchmark.trace import program_spans


def read(trace, counters, spans, cell):
    lo, hi = program_spans.stretch(spans)
    entries = program_spans.ring("fit_step_p95_ms", since=lo)
    if entries is None:
        return None
    steps = sorted(program_spans.whole_steps_ms(entries, lo, hi))
    if not steps:
        return None
    return steps[math.ceil(0.95 * len(steps)) - 1]
