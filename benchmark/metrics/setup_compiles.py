"""Device runtime: programs XLA had to build during set-up because the
persistent compile cache did not hold them (JAX's cache-miss events
before the window opened). Moves ``setup_s``."""


def read(trace, counters, spans, cell):
    return counters["setup_compiles"]
