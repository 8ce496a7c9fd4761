"""Readings the limits of ``limits/<workload>.json`` are set from
(PERF.md, "How ``correct`` is decided"), at the cell's own size on the chip:

    python3 benchmark/tools/readings.py --workload W --seeds 11,12,... \\
        --control-seeds 11,12,13 --controls int8_matmul,bf16_accumulate

For each seed the cell runs with a window of a fraction of a second (the
compared numbers need none) and prints each number beside its limit. For
each control seed the reference runs in the program's place at a precision
below the stated one (``reference/arrays.py: PRECISIONS``), on the same
seeded weights and the same first batch, and the number that tells
precisions apart is read from it by the same arithmetic (one forward
pass: no backward program is built for it). The last lines give the
largest sound reading and the smallest control reading of each number.
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
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--controls", default="int8_matmul,bf16_accumulate")
    args = p.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(os.path.dirname(BENCH), ".jax_cache"))
    from benchmark import harness
    from benchmark.drivers import fit
    from benchmark.reference import check, train

    cell = harness.load_cell(args.workload)
    sound, control = {}, {}
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        print("== sound run, seed %d" % seed, flush=True)
        res = fit.run(cell, seed=seed, seconds=0.25, trace=False,
                      t_start=time.perf_counter())
        for name, value, _, _ in res["rows"]:
            sound.setdefault(name, []).append(value)
    config, traffic = cell["config"], cell["traffic"]
    os.environ.update(config["env"])
    devices = harness.require_chips(cell["cell"]["chips"])
    ref = config["reference"]
    shape = (config["batch"],) + tuple(config["input_chw"])
    name, limit = "step1_excess_noise", cell["limits"]["step1_excess_noise"]
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        params0 = train.init_params(
            train.param_shapes(ref["net"], ref["args"], shape), seed)
        source = harness.load_by_name(
            "generators", traffic["generator"]).Source(
                traffic["params"], config, seed, devices)
        x = source.next().data[0]._data
        source.close()
        want = train.forward_logprob(ref["net"], ref["args"], params0, x)
        floor = train.forward_logprob(ref["net"], ref["args"], params0, x,
                                      precision=config["compute_dtype"])
        for prec in args.controls.split(","):
            t0 = time.perf_counter()
            got = train.forward_logprob(ref["net"], ref["args"], params0, x,
                                        precision=prec)
            value, g, f = check.excess_noise(got, floor, want)
            print("control %-16s seed %d  %s %.6g  limit %.6g  %s  (rms gap "
                  "to float32 %.5f, the stated precision's own %.5f, %.1f s)"
                  % (prec, seed, name, value, limit,
                     "fails, as it must" if value > limit else "PASSES",
                     g, f, time.perf_counter() - t0), flush=True)
            control.setdefault(prec, []).append(value)
    for key in sorted(sound):
        print("summary %-32s sound max %.6g min %.6g over %d"
              % (key, max(sound[key]), min(sound[key]), len(sound[key])))
    for prec in sorted(control):
        print("summary %-32s control %s min %.6g max %.6g over %d"
              % (name, prec, min(control[prec]), max(control[prec]),
                 len(control[prec])))


if __name__ == "__main__":
    main()
