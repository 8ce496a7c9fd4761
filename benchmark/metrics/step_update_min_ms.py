"""Train step: the least the chip needs for the instructions that hold
the optimizer's update, ms a step: over every set of the census
(``compile.fused_step.census.*``) that names ``update``, the larger of
its bytes over the chip's HBM rate and its matrix FLOPs over the peak at
the cell's compute dtype. A bound from the program's text, not a time:
what the device time of those instructions is to be held against. Prints
the census, a line a set."""


def read(trace, counters, spans, cell):
    from mxnet_tpu import telemetry

    census = telemetry.snapshot().get("compile", {}).get(
        "fused_step", {}).get("census")
    if not census:
        return None
    peaks = cell["peaks"]
    flops_per_s = peaks["flops_per_s"][cell["config"]["compute_dtype"]]
    total = 0.0
    for name, row in sorted(census.items()):
        t_bytes = 1e3 * row["bytes"] / peaks["hbm_bytes_per_s"]
        t_flops = 1e3 * row["flops"] / flops_per_s
        print("census %s: %d ops, %.6g FLOPs, %.6g bytes; least %.3f ms "
              "(%s bind: %.3f ms of FLOPs, %.3f ms of bytes)" % (
                  name, row["ops"], row["flops"], row["bytes"],
                  max(t_flops, t_bytes),
                  "FLOPs" if t_flops >= t_bytes else "bytes",
                  t_flops, t_bytes))
        if "update" in name.split("+"):
            total += max(t_flops, t_bytes)
    return total
