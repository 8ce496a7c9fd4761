"""Train step: programs built or read from the compile cache inside the
WHOLE measured window (JAX's cache hits and misses, the executor's
``executor.jit_build`` and the fused step's ``step.fused_recompiles``).
Must read 0: nothing compiles inside the window."""


def read(trace, counters, spans, cell):
    return counters["window_compiles"]
