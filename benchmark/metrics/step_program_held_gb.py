"""Train step: what the compiled step holds at once by XLA's own account,
GB: arguments + outputs - aliased (donated) + temporaries + code (the
program's gauge ``compile.fused_step.held_bytes``). The margin to the
chip's memory; the ring's other batches and what else the process keeps
on the device are not in it (``step_hbm_at_fence_gb`` has them)."""


def read(trace, counters, spans, cell):
    from mxnet_tpu import telemetry

    value = telemetry.peek("compile.fused_step.held_bytes", kind="gauge")
    return None if value is None else value / 1e9
