"""Device runtime: seconds from asking for the fused step's program to
holding it: trace, lower, and the cache's read or XLA's build (the
program's gauge ``compile.fused_step.build_s``). The largest single piece
of ``setup_s``, which it moves."""


def read(trace, counters, spans, cell):
    from mxnet_tpu import telemetry

    return telemetry.peek("compile.fused_step.build_s", kind="gauge")
