"""Readings the limits of a language-model cell are set from, at the cell's
own size on the chip (``tools/readings_lm.py`` with the driver taken from
the cell's configuration: any whose ``run`` takes ``controls``):

    python3 benchmark/tools/readings_lm_ref.py --workload W \\
        --seeds 11,12,... --controls int8_matmul,b_undoubled,...

For each seed the cell runs with a window of a fraction of a second (the
compared numbers need none), prints each number beside its limit and then
reads the reference's controls (one forward pass each, the stated
precision with one thing wrong) on the same seeded weights and the same
first batch. The last lines give the largest sound reading of each number
and the smallest control reading.
"""
import argparse
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", default="")
    p.add_argument("--control-first", type=int, default=1000,
                   help="read the controls on the first N seeds only")
    p.add_argument("--seconds", type=float, default=0.25)
    args = p.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(os.path.dirname(BENCH), ".jax_cache"))
    from benchmark import harness

    cell = harness.load_cell(args.workload)
    driver = harness.load_by_name("drivers", cell["config"]["driver"])
    controls = tuple(c for c in args.controls.split(",") if c)
    sound, control = {}, {}
    for n, seed in enumerate(int(s) for s in args.seeds.split(",") if s):
        print("== sound run, seed %d" % seed, flush=True)
        res = driver.run(
            cell, seed=seed, seconds=args.seconds, trace=False,
            t_start=time.perf_counter(),
            controls=controls if n < args.control_first else ())
        for name, value, _, _ in res["rows"]:
            sound.setdefault(name, []).append(value)
        for prec, value in res["controls"].items():
            control.setdefault(prec, []).append(value)
    for key in sorted(sound):
        print("summary %-32s sound max %.6g min %.6g over %d"
              % (key, max(sound[key]), min(sound[key]), len(sound[key])))
    for prec in sorted(control):
        print("summary %-32s control %s min %.6g max %.6g over %d"
              % ("step1_excess_noise", prec, min(control[prec]),
                 max(control[prec]), len(control[prec])))


if __name__ == "__main__":
    main()
