"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json`` on the machine it is started
on. The driver the configuration names does the work and prints the
result as the last line of stdout. It times set-up (import of the
program, data and weights from the seed, bind, compile or cache read,
warm-up) from the moment JAX has its devices; how long the process took
to get there (Python, ``import jax``, the TPU runtime's own start) is
printed from the clock started here and is in no metric.
Without the TPU devices the cell asks for it exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    # the persistent compile cache: where the environment says, else a
    # fixed path inside this checkout (the path is part of the key)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    from benchmark import harness

    cell = harness.load_cell(args.workload)
    driver = harness.load_by_name("drivers", cell["config"]["driver"])
    print("process +%.2f s: JAX and the driver are imported"
          % (time.perf_counter() - T_START), flush=True)
    driver.run(cell, seed=args.seed, seconds=args.seconds,
               trace=bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    main()
