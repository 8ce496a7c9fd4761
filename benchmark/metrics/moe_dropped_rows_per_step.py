"""Train step: pairs a step that landed here and were not computed
(``moe.dropped_rows``): 0 by construction, counted so that a change that
drops shows."""


def read(trace, counters, spans, cell):
    if not counters.get("moe.rows_total"):
        return None
    return counters["moe.dropped_rows"] / counters["steps"]
