"""Train step: compiled entries the fused step's jits hold after the
window (the program's gauge ``step.fused_jit_entries``, what the jits
themselves count). 1 where one program serves every step."""


def read(trace, counters, spans, cell):
    from mxnet_tpu import telemetry

    return telemetry.peek("step.fused_jit_entries", kind="gauge")
