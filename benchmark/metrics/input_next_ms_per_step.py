"""Input: time per step the program's ``fit.next`` spans (the ``next()``
on ``fit``'s iterator, its wrappers included) cover of the traced
stretch. Timed from inside ``fit``; the harness's ``iter.next`` span
times the same call from outside."""
from benchmark.trace import program_spans


def read(trace, counters, spans, cell):
    lo, hi = program_spans.stretch(spans)
    entries = program_spans.ring("input_next_ms_per_step", since=lo)
    if entries is None or not counters["steps"]:
        return None
    return program_spans.covered(entries, ("fit.next",), lo, hi) * 1e3 \
        / counters["steps"]
