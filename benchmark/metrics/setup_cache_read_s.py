"""Device runtime: seconds JAX spent getting executables before the
traced stretch began: reading them from the persistent cache or having
XLA build them (the program's ``jax.cache_read`` and
``jax.backend_compile`` spans; the second encloses the first), less what
tracing and lowering cover. Moves ``setup_s``."""
from benchmark.trace import program_spans as ps


def read(trace, counters, spans, cell):
    before = ps.before_stretch("setup_cache_read_s", spans)
    if before is None:
        return None
    return ps.covered(before, ps.JAX_BUILD, less=ps.JAX_TRACE_LOWER)
