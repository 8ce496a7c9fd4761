"""Device runtime: seconds of set-up inside the program's ``fit.bind``,
``fit.init_params``, ``fit.init_optimizer`` and ``fit.fused_build`` spans
that no ``jax.*`` span covers: binding's own Python and the dispatches
it makes, with tracing, lowering and building counted by their own
metrics. Moves ``setup_s``."""
from benchmark.trace import program_spans as ps


def read(trace, counters, spans, cell):
    entries = ps.ring("setup_bind_s")
    if entries is None:
        return None
    return ps.covered(entries, ps.SETUP,
                      less=ps.JAX_TRACE_LOWER + ps.JAX_BUILD)
