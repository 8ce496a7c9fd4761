"""How a language-model configuration's learning rate was tried (its
``assumed.fit``): the cell's own model, weights and traffic from one seed
through ``Module.fit`` for a few dozen steps at each rate, the loss of every
step printed. The loss must fall over the window's steps from seeded
weights, and the first three steps must not be chaotic (a step that doubles
the loss cannot be followed by a float32 reference).

    python3 benchmark/tools/sweep_lr.py --workload W --seed 1 --steps 30 \\
        --rates 1e-4,3e-5,1e-5
"""
import argparse
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--rates", required=True)
    args = p.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(os.path.dirname(BENCH), ".jax_cache"))
    from benchmark import harness
    from benchmark.drivers import fit
    from benchmark.reference import train

    cell = harness.load_cell(args.workload)
    config, traffic = cell["config"], cell["traffic"]
    os.environ.update(config["env"])
    devices = harness.require_chips(1)

    import jax
    import numpy as np

    import mxnet_tpu as mx

    ref = harness.load_by_name("reference", config["reference"]["net"])
    with jax.default_device(devices[0]):
        w0 = {k: np.asarray(v) for k, v in ref.init_params(
            config["reference"]["args"], train.seed_key(args.seed),
            tuple(config["init"]["time_step"])).items()}
    tokens = config["tokens"]["batch"] * config["tokens"]["seq_len"]
    for rate in [float(r) for r in args.rates.split(",")]:
        source = harness.load_by_name(
            "generators", traffic["generator"]).Source(
                traffic["params"], config, args.seed, devices)
        metric = mx.metric.create(config["fit"]["eval_metric"])
        losses, seen = [], [0.0]

        class Steps:
            batch_size = source.batch_size
            provide_data = source.provide_data
            provide_label = source.provide_label
            k = 0

            def reset(self):
                pass

            def __iter__(self):
                return self

            def __next__(self):
                if self.k:
                    total = metric.get()[1] * self.k * tokens
                    losses.append((total - seen[0]) / tokens)
                    seen[0] = total
                if self.k == args.steps:
                    raise StopIteration
                self.k += 1
                return source.next()

        net = fit._factory(config["model"]["factory"])(
            **config["model"]["args"])
        mod = mx.mod.Module(net, context=[mx.tpu(0)])
        recipe = dict(config["fit"]["optimizer_params"], learning_rate=rate)
        args_of = set(net.list_arguments())
        mod.fit(Steps(), eval_metric=metric, kvstore="local",
                optimizer=config["fit"]["optimizer"],
                optimizer_params=recipe, initializer=None,
                arg_params={k: mx.nd.array(v, ctx=mx.cpu(0))
                            for k, v in w0.items() if k in args_of},
                aux_params={k: mx.nd.array(v, ctx=mx.cpu(0))
                            for k, v in w0.items() if k not in args_of},
                num_epoch=1)
        print("rate %g: %s" % (rate, " ".join("%.3f" % v for v in losses)),
              flush=True)
        source.close()
        del mod


if __name__ == "__main__":
    main()
