"""Train step: (row, expert) pairs a step that landed on the experts held
here, over all expert layers: the program's ``moe.rows_here`` counter, fed
at the traced stretch's fences from the row counts the op keeps on the
device."""


def read(trace, counters, spans, cell):
    if not counters.get("moe.rows_total"):
        return None
    return counters["moe.rows_here"] / counters["steps"]
