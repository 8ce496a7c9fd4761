"""Run one traced cell of the ``fit_lm_ref`` driver and keep a cut of its
device events WITH their scope paths (``trace/scopes.py``'s form) under
``chiprun_out/``: how ``tests/data/<workload>.scopes.json.gz`` was recorded
(``tools/record_scopes.py`` for that driver: the parts are the
configuration's reference's, and its arguments are kept beside the cut).
Arguments are ``run.py``'s, preceded by the milliseconds to keep from the
middle of the traced stretch:  python3 benchmark/tools/record_scopes_ref.py
1200 --workload W --seed 1 --seconds 10 --trace 1
"""
import gzip
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))


def main():
    keep_ns = float(sys.argv[1]) * 1e6
    from benchmark import harness, run
    from benchmark.trace import scopes

    by_part = scopes.by_part
    workload = sys.argv[sys.argv.index("--workload") + 1]
    args = harness.load_cell(workload)["config"]["reference"]["args"]

    def by_part_and_keep(events, lo, hi, part_of):
        mid = (lo + hi) / 2.0
        cut_lo, cut_hi = mid, mid + keep_ns
        cut = [list(e) for e in events
               if e[2] >= cut_lo and e[2] + e[3] <= cut_hi]
        os.makedirs("chiprun_out", exist_ok=True)
        path = os.path.join("chiprun_out", workload)
        with gzip.open(path + ".scopes.json.gz", "wt") as f:
            json.dump({"lo": cut_lo, "hi": cut_hi, "events": cut}, f,
                      separators=(",", ":"))
        got = by_part([tuple(e) for e in cut], cut_lo, cut_hi, part_of)
        with open(path + ".scopes.expect.json", "w") as f:
            json.dump({"args": args, "by_part_s": got}, f, indent=1)
        print("kept %d of %d device events (%.0f ms): %s" % (
            len(cut), len(events), keep_ns / 1e6, path + ".scopes.json.gz"))
        return by_part(events, lo, hi, part_of)

    scopes.by_part = by_part_and_keep
    run.main(sys.argv[2:])


if __name__ == "__main__":
    main()
