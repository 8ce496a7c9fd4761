"""Kernels: the least time the chip could take for one training step over the
time it was busy for it. The least time is the larger of the reference's
useful FLOPs (three passes; the recomputed forward is not useful) over peak
FLOP/s and its least bytes over peak HBM bytes/s
(``reference/nemotron_h.py: step_cost``; ``peaks.json``)."""


def read(trace, counters, spans, cell):
    if "step_parts" not in cell:
        return None
    peaks, chips = cell["peaks"], cell["chips"]
    dtype = cell["config"]["compute_dtype"]
    t_flops = cell["step_flops"] / (peaks["flops_per_s"][dtype] * chips)
    t_bytes = cell["step_bytes"] / (peaks["hbm_bytes_per_s"] * chips)
    least = max(t_flops, t_bytes)
    print("roofline: least %.3f ms a step (%s bind: %.3f ms of FLOPs, "
          "%.3f ms of bytes)" % (least * 1e3,
                                 "FLOPs" if t_flops >= t_bytes else "bytes",
                                 t_flops * 1e3, t_bytes * 1e3))
    return 100.0 * least / (trace["busy_s"] / trace["steps"])
