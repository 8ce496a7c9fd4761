"""Kernels: device time a step under ``CausalAttention`` of the
sliding-window layers: the relayout passes with rotary, the splash forward
kernel under its banded mask and the one-kernel backward pass over the
band's pairs of blocks (part ``attention_window_kernel`` of
``reference/laguna.py``; forward and backward together). The full layers'
op is ``attention_kernel``, in ``attention_ms_per_step`` with both kinds'
projections."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("attention_window_kernel",))
