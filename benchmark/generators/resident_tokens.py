"""Traffic generator ``resident_tokens``: ``ring`` distinct seeded batches
of packed token sequences made ON the device in set-up and handed out in a
cycle for ever, int32 ids and next-token labels. Nothing crosses the host
link inside the window (0 host bytes a step).

Each batch is ``config["tokens"]["batch"]`` sequences of ``seq_len``
positions. A sequence is packed end to end, no padding, from documents
whose lengths are log-normal (``doc_median``, ``doc_sigma``, cut at the
sequence length): a document ends in the separator, id 0, and the next
begins at once, so attention and state run across document boundaries as
pre-training packs. Token ids are Zipf (``zipf_exponent``) over the ids 1
.. vocab-1 of the configuration's slice of the vocabulary (rank r is id r):
a few ids make most of the stream, which is what loads experts unevenly,
and a unigram distribution a few hundred steps can learn, so "the loss
falls" is a test.

Parameters (the traffic file's ``params``): ``ring``, ``doc_median``,
``doc_sigma``, ``zipf_exponent``.
"""
import math

import numpy as np

MAX_DOCS = 512      # boundaries drawn a sequence; the rest has none


class Source:
    def __init__(self, params, config, seed, devices):
        import jax
        import jax.numpy as jnp

        import mxnet_tpu as mx
        from mxnet_tpu.io import DataBatch, DataDesc

        from benchmark.reference.train import seed_key

        self._DataBatch = DataBatch
        batch = config["tokens"]["batch"]
        t = config["tokens"]["seq_len"]
        vocab = config["model"]["args"]["vocab"]
        self.batch_size = batch
        self.provide_data = [DataDesc("data", (batch, t))]
        self.provide_label = [DataDesc("softmax_label", (batch, t))]
        self.h2d_bytes_inside = 0
        weights = np.arange(1, vocab, dtype=np.float64) \
            ** -float(params["zipf_exponent"])
        cdf = jnp.asarray(np.cumsum(weights) / weights.sum(), jnp.float32)
        mu, sigma = math.log(params["doc_median"]), params["doc_sigma"]

        def make(key):
            k1, k2 = jax.random.split(key)
            u = jax.random.uniform(k1, (batch, t + 1), jnp.float32)
            ids = 1 + jnp.searchsorted(cdf, u).astype(jnp.int32)
            ids = jnp.minimum(ids, vocab - 1)
            lengths = jnp.exp(mu + sigma * jax.random.normal(
                k2, (batch, MAX_DOCS), jnp.float32))
            ends = jnp.cumsum(jnp.clip(jnp.round(lengths), 1, t).astype(
                jnp.int32), axis=1) - 1
            # a separator where a document ends (ends past the sequence
            # fall off: mode="drop")
            rows = jnp.arange(batch)[:, None]
            ids = ids.at[rows, ends].set(0, mode="drop")
            return ids[:, :-1], ids[:, 1:]

        device = devices[0]
        make = jax.jit(make)
        key = seed_key(seed)
        ctx = mx.tpu(0) if device.platform != "cpu" else mx.cpu(0)
        self._ring = []
        with jax.default_device(device):
            for i in range(params["ring"]):
                ids, labels = make(jax.random.fold_in(key, 1000 + i))
                self._ring.append((mx.nd.NDArray(ids, ctx=ctx),
                                   mx.nd.NDArray(labels, ctx=ctx)))
        self._k = 0

    def next(self):
        data, label = self._ring[self._k % len(self._ring)]
        self._k += 1
        return self._DataBatch([data], [label], pad=0)

    def last(self):
        """The ring's last batch as device arrays ``(ids, labels)``,
        without handing it out: the one before the first, in the cycle."""
        return tuple(a._data for a in self._ring[-1])

    def check(self, captured):
        """Nothing of the program lies between this generator and the
        step: the rows handed over are the rows made."""
        return []

    def close(self):
        self._ring = []
