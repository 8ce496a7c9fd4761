"""Device time of one operator's scope, by the primitive that made each
device op: reads a cut kept by ``benchmark/tools/record_scopes.py`` /
``record_scopes_ref.py`` (``chiprun_out/<workload>.scopes.json.gz``).

    python tools/scope_cut.py <cut.json.gz> RoutedExperts [period_ms]

Prints, per primitive path under ``<op>:<node>`` (``jit(argsort)/sort``,
``scatter-add``, ``gather``, a kernel's name; ``again/`` marks the
recomputed forward), the self time in the cut; then the same by the device
op's own name and its result's shape. With ``period_ms``, a step's time end
to end (``step_device_ms`` + ``fit_host_gap_ms_per_step``), only the whole
periods from the cut's start are read, so that every op is in it as often
as every other, and the times are ms a step. Self time is
``benchmark/trace/scopes.py``'s, as ``by_part`` counts it."""
import collections
import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _result_of(name):
    """``fusion f32[65536]`` of ``%fusion.68 = f32[65536]{0:T(1024)} ...``:
    the device op without its number, and its result's shape."""
    head, _, rest = name.lstrip("%").partition(" = ")
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return "%s %s" % (re.sub(r"[.][0-9]+$", "", head),
                      shape.group(1) if shape else "")


def main():
    from benchmark.trace.scopes import self_times

    path, op = sys.argv[1], sys.argv[2]
    period_ms = float(sys.argv[3]) if len(sys.argv) > 3 else None
    with gzip.open(path, "rt") as f:
        cut = json.load(f)
    lo, hi = cut["lo"], cut["hi"]
    steps = int((hi - lo) / 1e6 // period_ms) if period_ms else 1
    if period_ms:
        hi = lo + steps * period_ms * 1e6
    events = [tuple(e) for e in cut["events"] if e[2] + e[3] <= hi]
    node = re.compile(r"(fwd|bwd|update|metric)/.*%s:[A-Za-z0-9_+]+\)*/?(.*)$"
                      % re.escape(op))
    by_prim, by_name = collections.Counter(), collections.Counter()
    for name, scope, ns in self_times(events):
        m = node.search(scope)
        if not m:
            continue
        again = "again/" if "rematted_computation" in scope else ""
        prim = re.sub(r"/cond/branch_\d+_fun|/pallas_call|:$", "", m.group(2))
        by_prim["%s/%s%s" % (m.group(1), again, prim or "(the op)")] += ns
        by_name[_result_of(name)] += ns
    print("%s: %.3f ms %s" % (
        op, sum(by_prim.values()) / 1e6 / steps,
        "a step, over %d steps of %.2f ms" % (steps, period_ms)
        if period_ms else "of a %.0f ms cut" % ((hi - lo) / 1e6)))
    for title, table in (("by primitive", by_prim), ("by device op", by_name)):
        print(title)
        for key, ns in table.most_common(40):
            print("  %-58.58s %9.3f" % (key, ns / 1e6 / steps))


if __name__ == "__main__":
    main()
