"""Device runtime: seconds JAX spent tracing programs to jaxprs and
lowering them to MLIR (the program's ``jax.trace`` and ``jax.lower``
spans, from JAX's own duration events) before the traced stretch began.
Moves ``setup_s``."""
from benchmark.trace import program_spans as ps


def read(trace, counters, spans, cell):
    before = ps.before_stretch("setup_trace_lower_s", spans)
    if before is None:
        return None
    return ps.covered(before, ps.JAX_TRACE_LOWER)
