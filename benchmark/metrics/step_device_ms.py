"""Train step: time the device was busy (union of its operations'
intervals, mean over the chips) per step."""


def read(trace, counters, spans, cell):
    return trace["busy_s"] * 1e3 / trace["steps"]
