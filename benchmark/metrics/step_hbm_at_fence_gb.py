"""Device: what the runtime holds at a fence, GB: ``bytes_in_use`` +
``bytes_reserved`` of ONE ``memory_stats()`` call on the step's first
device (the program's gauges ``device.hbm_in_use_bytes`` and
``device.hbm_reserved_bytes``, set together by
``Module.publish_aux_counters`` at the last fence before ``fit``
returned). Live buffers and the loaded programs' temporaries at the same
moment, where ``peak_hbm_gb`` + ``peak_hbm_reserved_gb`` add two peaks.
Nothing where either is absent: a backend with no allocator to ask (the
CPU) publishes neither."""


def read(trace, counters, spans, cell):
    from mxnet_tpu import telemetry

    in_use = telemetry.peek("device.hbm_in_use_bytes", kind="gauge")
    reserved = telemetry.peek("device.hbm_reserved_bytes", kind="gauge")
    if in_use is None or reserved is None:
        return None
    return (in_use + reserved) / 1e9
