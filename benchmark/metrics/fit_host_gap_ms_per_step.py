"""Fit loop: device-idle time inside the traced window that fell under
the harness's ``fit_loop`` span (fit's own Python between two calls of
the iterator), per step."""


def read(trace, counters, spans, cell):
    return trace["idle_by_span_s"].get("fit_loop", 0.0) * 1e3 \
        / trace["steps"]
