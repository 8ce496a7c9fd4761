"""Kernels: the pairs of blocks the windowed layers' backward kernel walks
over the pairs the causal half of the same blocks holds (the program's
``lower.attention_window.block_pairs`` / ``.block_pairs_causal``, counted
once a traced windowed op: they stand where the step was traced, before the
traced stretch, so they are read whole and not as the stretch's change).
Nothing in a program that counts no band."""


def read(trace, counters, spans, cell):
    from mxnet_tpu import telemetry

    band = telemetry.peek("lower.attention_window.block_pairs")
    causal = telemetry.peek("lower.attention_window.block_pairs_causal")
    if not band or not causal:
        return None
    return band / causal
