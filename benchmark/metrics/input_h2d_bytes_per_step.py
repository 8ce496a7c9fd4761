"""Input: bytes that cross the host link per step: the arrays the
iterator hands over that are not yet on the module's devices, plus what
the traffic generator says its iterator ships itself before handing
over (``h2d_bytes_inside``)."""


def read(trace, counters, spans, cell):
    return counters["h2d_bytes"] / counters["steps"]
