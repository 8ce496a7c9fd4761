"""Input: time the loop waited for the traffic generator's ``next()``
(the harness's ``input.next`` span) plus the program's own
``io.feed_stall_ms``, per step."""


def read(trace, counters, spans, cell):
    waited = sum(t1 - t0 for name, t0, t1 in spans if name == "input.next")
    return (waited * 1e3 + counters["io.feed_stall_ms"]) / counters["steps"]
