"""Kernels: device time a step under softmax attention outside the kernel:
the query-and-gate, key and value projections, the slices that part query
from gate, the two head norms, the gate's sigmoid and product, the output
projection, and the block's norm and add (part ``attention_proj`` of
``reference/qwen3_next.py``, which ``mla_proj_ms_per_step`` reads for the
latent cells; forward, recomputed forward and backward together)."""
from benchmark.trace import scopes


def read(trace, counters, spans, cell):
    return scopes.part_ms(trace, ("attention_proj",))
