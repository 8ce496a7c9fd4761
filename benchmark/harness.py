"""What every driver shares: finding a cell's files by the names in
``BENCHMARK.json``, the look for the chip, the device record, the
per-layer readers, and the last line.

Adding a configuration, a traffic mix, a driver or a per-layer metric is
adding a file and an entry (``README.md``); nothing here names one.
"""
import importlib
import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _load(path):
    with open(path) as f:
        return json.load(f)


def load_cell(workload):
    """The cell's entry with its configuration, traffic mix and limits,
    each from the file its name points at."""
    spec = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit("no workload %r in BENCHMARK.json (have: %s)"
                         % (workload, ", ".join(sorted(cells))))
    cell = cells[workload]
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = _load(os.path.join(ROOT, entry["file"]))
    traffic = _load(os.path.join(BENCH, "traffic",
                                 cell["traffic"] + ".json"))
    limits = _load(os.path.join(BENCH, "limits", workload + ".json"))
    return {"spec": spec, "cell": cell, "config": config,
            "traffic": traffic, "limits": limits["limits"]}


def require_chips(n):
    """The first ``n`` TPU devices, or exit non-zero with no result: a
    number from another platform is never written under a device
    metric's name."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit("benchmark: no TPU: jax.devices()[0].platform is "
                         "%r" % devs[0].platform)
    if len(devs) < n:
        raise SystemExit("benchmark: the cell needs %d chips, JAX found %d"
                         % (n, len(devs)))
    return devs[:n]


def peaks(device_kind):
    table = _load(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table:
        raise SystemExit("benchmark: device kind %r is not in peaks.json"
                         % device_kind)
    return table[device_kind]


def memory_peaks(devices):
    """Of the fullest chip, the allocator's two peaks: (live buffers in
    use, what the runtime reserved for compiled programs: their
    temporaries live there on the TPU and never show as buffers in use;
    the run prints what shows that the two do not overlap)."""
    stats = [d.memory_stats() or {} for d in devices]
    return (max(s.get("peak_bytes_in_use", 0) for s in stats),
            max(s.get("peak_bytes_reserved", 0) for s in stats))


def device_record(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": sum(memory_peaks(devices))}


def metric_names(spec, kind, cell_name):
    """The ``kind`` ('end_to_end' / 'per_layer') metrics this cell
    reports: those that list it, and those that list no cells."""
    return [m["name"] for m in spec[kind]
            if cell_name in m.get("workloads", [cell_name])]


def read_per_layer(spec, cell_name, trace, counters, spans, cell):
    """Each per-layer metric from its own reader,
    ``metrics/<name>.py: read(trace, counters, spans, cell)``. A reader
    that finds nothing to read returns None and the metric is left out."""
    units = {m["name"]: m["unit"] for m in spec["per_layer"]}
    out = {}
    for name in metric_names(spec, "per_layer", cell_name):
        path = os.path.join(BENCH, "metrics", name + ".py")
        mod_spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        value = mod.read(trace, counters, spans, cell)
        if value is not None:
            out[name] = {"value": float(value), "unit": units[name]}
    return out


def load_by_name(package, name):
    return importlib.import_module("benchmark.%s.%s" % (package, name))


def last_line(correct, attempted, failed, metrics, device, breakdown=None):
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
