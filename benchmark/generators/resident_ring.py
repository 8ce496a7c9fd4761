"""Traffic generator ``resident_ring``: ``ring`` distinct seeded batches
made ON the device in set-up and handed out in a cycle for ever. Nothing
crosses the host link inside the window, so the input layer is bypassed:
what is left is the fit loop, the train step and the kernels.

Parameters (the traffic file's ``params``): ``ring`` (batches),
``used_classes``, ``noise`` (standard deviation around the prototype).
"""
import numpy as np

from . import synthetic


class Source:
    def __init__(self, params, config, seed, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        import mxnet_tpu as mx
        from mxnet_tpu.io import DataBatch, DataDesc

        self._DataBatch = DataBatch
        batch, chw = config["batch"], tuple(config["input_chw"])
        self.batch_size = batch
        self.provide_data = [DataDesc("data", (batch,) + chw)]
        self.provide_label = [DataDesc("softmax_label", (batch,))]
        self.h2d_bytes_inside = 0
        used, noise = params["used_classes"], params["noise"]
        rng = np.random.default_rng([int(seed), 1])
        protos = synthetic.class_protos(rng, used, chw)
        # rows of the batch over the chips exactly as the module's group
        # lays a batch out (one axis over all its devices), so that
        # handing a batch over moves nothing
        mesh = Mesh(np.array(devices), ("dp",))
        rows = NamedSharding(mesh, P("dp"))
        protos = jax.device_put(protos, NamedSharding(mesh, P()))

        def make(key, protos):
            k1, k2 = jax.random.split(key)
            labels = jax.random.randint(k1, (batch,), 0, used)
            data = protos[labels] + noise * jax.random.normal(
                k2, (batch,) + chw, jnp.float32)
            return data, labels.astype(jnp.float32)

        make = jax.jit(make, out_shardings=(rows, rows))
        from benchmark.reference.train import seed_key

        key = seed_key(seed)
        ctx = mx.tpu(0) if devices[0].platform != "cpu" else mx.cpu(0)
        self._ring = []
        for i in range(params["ring"]):
            data, labels = make(jax.random.fold_in(key, i), protos)
            self._ring.append((mx.nd.NDArray(data, ctx=ctx),
                               mx.nd.NDArray(labels, ctx=ctx)))
        self._k = 0

    def next(self):
        data, label = self._ring[self._k % len(self._ring)]
        self._k += 1
        return self._DataBatch([data], [label], pad=0)

    def check(self, captured):
        """Nothing of the program lies between this generator and the
        step: the rows handed over are the rows made."""
        return []

    def close(self):
        self._ring = []
