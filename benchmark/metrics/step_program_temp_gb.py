"""Train step: the temporaries of the compiled step, GB: XLA's
``memory_analysis().temp_size_in_bytes`` of the fused step's one
executable (the program's gauge ``compile.fused_step.temp_bytes``, set
when a step is built with telemetry on). What recomputation and a
kernel's residuals move."""


def read(trace, counters, spans, cell):
    from mxnet_tpu import telemetry

    value = telemetry.peek("compile.fused_step.temp_bytes", kind="gauge")
    return None if value is None else value / 1e9
