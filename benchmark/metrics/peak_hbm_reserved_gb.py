"""Device: the allocator's ``peak_bytes_reserved`` on the fullest chip
after the window, in GB: what the runtime set aside for the compiled
programs, the train step's activations and other temporaries among it.
``memory_peak_bytes`` on the line is this plus ``peak_hbm_gb``."""


def read(trace, counters, spans, cell):
    return cell["memory_peak_reserved_bytes"] / 1e9
