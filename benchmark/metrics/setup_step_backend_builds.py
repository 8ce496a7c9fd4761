"""Device runtime: whether XLA had to BUILD the one program the window
times: 1 unless the persistent cache answered for the fused step (the
program's gauge ``compile.fused_step.cache_read``, from JAX's cache events
inside that build). 0 in a warm run; 1 where the cache is off.
``setup_compiles`` counts every program's miss and names none. Moves
``setup_s``."""


def read(trace, counters, spans, cell):
    from mxnet_tpu import telemetry

    read_back = telemetry.peek("compile.fused_step.cache_read",
                               kind="gauge")
    return None if read_back is None else 1 - read_back
