"""Train step: over the traced stretch, the busiest held expert's rows over
the mean held expert's, of the worst expert layer (gauge
``moe.expert_load_max_over_mean``): what a capacity would have to be."""


def read(trace, counters, spans, cell):
    if not counters.get("moe.rows_total"):
        return None
    return counters.get("moe.expert_load_max_over_mean") or None
