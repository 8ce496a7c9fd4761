"""Device: the allocator's ``peak_bytes_in_use`` on the fullest chip after
the window, in GB: live buffers (weights, optimizer state, the resident
ring). Compiled programs' temporaries are not in it: see
``peak_hbm_reserved_gb``."""


def read(trace, counters, spans, cell):
    return cell["memory_peak_in_use_bytes"] / 1e9
