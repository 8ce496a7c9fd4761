"""Run one traced cell and keep a cut of its trace, in ``xplane.load``'s
form, under ``chiprun_out/`` — how ``tests/data/*.trace.json.gz`` were
recorded. Arguments are ``run.py``'s, preceded by the number of steps to
keep:  python3 benchmark/tools/record_trace.py 2 --workload W --seed 1
--seconds 8 --trace 1
"""
import gzip
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))


def main():
    keep_steps = int(sys.argv[1])
    from benchmark import run
    from benchmark.trace import reduce as trace_reduce
    from benchmark.trace import xplane

    load = xplane.load

    def load_and_keep(trace_dir):
        trace = load(trace_dir)
        spans = trace_reduce.host_spans(trace)
        loops = [s for s in spans if s[0] == "fit_loop"]
        # steady state, not the run-ahead after the opening fence
        mid = max(0, min(len(loops) // 2, len(loops) - keep_steps - 1))
        print("trace holds %d fit_loop spans of %d spans; lines: %s" % (
            len(loops), len(spans),
            [(p["name"], ln["name"], len(ln["events"]))
             for p in trace["planes"] for ln in p["lines"]]))
        lo, hi = loops[mid][1] - 1e6, loops[mid + keep_steps][1] + 1e6
        cut = {"planes": []}
        for plane in trace["planes"]:
            lines = []
            for line in plane["lines"]:
                device = plane["name"].startswith("/device:")
                events = [e for e in line["events"]
                          if lo <= e[2] and e[2] + e[3] <= hi
                          and (device or e[0].startswith(
                              trace_reduce.SPAN_PREFIX))]
                if events:
                    lines.append({"name": line["name"], "events": events})
            if lines:
                cut["planes"].append({"name": plane["name"],
                                      "lines": lines})
        os.makedirs("chiprun_out", exist_ok=True)
        workload = sys.argv[sys.argv.index("--workload") + 1]
        path = os.path.join("chiprun_out", workload + ".trace.json.gz")
        with gzip.open(path, "wt") as f:
            json.dump(cut, f, separators=(",", ":"))
        got = trace_reduce.reduce(cut, keep_steps)
        with open(path.replace(".trace.json.gz", ".expect.json"), "w") as f:
            json.dump({"steps": keep_steps, "devices": got["devices"],
                       "values": {k: got[k] for k in (
                           "window_s", "busy_s", "conv_dot_s",
                           "collective_s", "collective_exposed_s")}},
                      f, indent=1)
        print("kept %s: %d planes" % (path, len(cut["planes"])))
        for plane in trace["planes"]:
            for line in plane["lines"]:
                print("trace line %s | %s: %d events" % (
                    plane["name"], line["name"], len(line["events"])))
        return trace

    xplane.load = load_and_keep
    run.main(sys.argv[2:])


if __name__ == "__main__":
    main()
