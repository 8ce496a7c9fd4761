"""Fit loop: XLA computations launched per step (the program's
``step.dispatches`` counter over the traced steps). 1.0 on the fused
step; the classic loop's forward-backward and update make 2."""


def read(trace, counters, spans, cell):
    return counters["step.dispatches"] / counters["steps"]
