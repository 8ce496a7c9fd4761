"""Time-major RNN language model (reference
example/rnn-time-major/rnn_cell_demo.py + bucket_io.py time_major=True).

Exercises the time-major layout path end to end:
  * an iterator whose ``provide_data`` declares layout ``"TN"`` — the
    batch axis is 1, so ``DataParallelExecutorGroup`` slices/pads along
    ``major_axis`` 1 (reference ``executor_group.py:16-66``
    layout-aware slicing, ``io.py:23-80`` LayoutMapper);
  * the fused ``RNN`` symbol consuming (T, N, F) directly — on TPU the
    time axis is the ``lax.scan`` carry dimension, so time-major is the
    layout the compiled step already wants (the reference measured
    time-major 1.5-2x faster than batch-major; here it avoids any
    transpose between embedding and scan);
  * ``SoftmaxOutput(preserve_shape=True)`` with (T, N) labels;
  * initial RNN states fed as data from the iterator (reference
    ``init_states`` convention) rather than learned parameters.

Task (zero-egress stand-in for PTB): predict the next token of
deterministic arithmetic sequences x[t+1] = (x[t] + step) % V with the
step identifying each sequence. Perplexity must fall well below the
uniform-guess baseline V after two epochs.
"""
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.io import DataBatch, DataDesc, DataIter

logging.basicConfig(level=logging.INFO)

VOCAB = 8
SEQ_LEN = 12
BATCH = 16
HIDDEN = 32
LAYERS = 1


class TimeMajorIter(DataIter):
    """Yields (T, N) token batches plus zero initial states (reference
    BucketSentenceIter(time_major=True) + init_states)."""

    def __init__(self, num_batches, seed):
        super().__init__()
        self.batch_size = BATCH
        rng = np.random.RandomState(seed)
        self._batches = []
        for _ in range(num_batches):
            start = rng.randint(0, VOCAB, size=BATCH)
            step = rng.randint(1, VOCAB, size=BATCH)
            t = np.arange(SEQ_LEN + 1)[:, None]
            seq = (start[None, :] + t * step[None, :]) % VOCAB  # (T+1, N)
            self._batches.append((seq[:-1].astype(np.float32),
                                  seq[1:].astype(np.float32)))
        self._i = -1

    @property
    def provide_data(self):
        shapes = [
            DataDesc("data", (SEQ_LEN, BATCH), layout="TN"),
            DataDesc("rnn_state", (LAYERS, BATCH, HIDDEN), layout="LNC"),
            DataDesc("rnn_state_cell", (LAYERS, BATCH, HIDDEN),
                     layout="LNC"),
        ]
        return shapes

    @property
    def provide_label(self):
        return [DataDesc("softmax_label", (SEQ_LEN, BATCH), layout="TN")]

    def reset(self):
        self._i = -1

    def iter_next(self):
        self._i += 1
        return self._i < len(self._batches)

    def getdata(self):
        data, _ = self._batches[self._i]
        zeros = mx.nd.zeros((LAYERS, BATCH, HIDDEN))
        return [mx.nd.array(data), zeros,
                mx.nd.zeros((LAYERS, BATCH, HIDDEN))]

    def getlabel(self):
        return [mx.nd.array(self._batches[self._i][1])]


def sym_gen():
    data = mx.sym.Variable("data")              # (T, N) token ids
    label = mx.sym.Variable("softmax_label")    # (T, N)
    embed = mx.sym.Embedding(data=data, input_dim=VOCAB,
                             output_dim=HIDDEN, name="embed")  # (T, N, H)
    rnn = mx.sym.RNN(data=embed,
                     state=mx.sym.Variable("rnn_state"),
                     state_cell=mx.sym.Variable("rnn_state_cell"),
                     parameters=mx.sym.Variable("rnn_parameters"),
                     state_size=HIDDEN, num_layers=LAYERS,
                     mode="lstm", name="rnn")   # (T, N, H)
    hidden = mx.sym.Reshape(data=rnn, shape=(-1, HIDDEN))
    pred = mx.sym.FullyConnected(data=hidden, num_hidden=VOCAB,
                                 name="pred")
    pred_tm = mx.sym.Reshape(data=pred, shape=(SEQ_LEN, -1, VOCAB))
    sm = mx.sym.SoftmaxOutput(data=pred_tm, label=label,
                              preserve_shape=True, name="softmax")
    return sm


def perplexity(label, pred):
    label = label.reshape(-1).astype(int)
    pred = pred.reshape(-1, pred.shape[-1])
    probs = np.maximum(pred[np.arange(len(label)), label], 1e-10)
    return float(np.exp(-np.log(probs).mean()))


def main():
    train = TimeMajorIter(num_batches=30, seed=0)
    val = TimeMajorIter(num_batches=4, seed=1)

    mod = mx.mod.Module(sym_gen(), context=mx.cpu(),
                        data_names=["data", "rnn_state", "rnn_state_cell"],
                        label_names=["softmax_label"])
    metric = mx.metric.np_metric(perplexity, name="perplexity")
    mod.fit(train, eval_data=val, num_epoch=4, eval_metric=metric,
            initializer=mx.initializer.Xavier(factor_type="in",
                                              magnitude=2.34),
            optimizer="adam", optimizer_params={"learning_rate": 0.01})

    score = dict(mod.score(val, mx.metric.np_metric(perplexity,
                                                    name="perplexity")))
    ppl = next(iter(score.values()))
    logging.info("validation perplexity %.3f (uniform baseline %d)",
                 ppl, VOCAB)
    assert ppl < 2.0, score
    # confirm the layout really is time-major through the module path
    assert DataDesc.get_batch_axis(train.provide_data[0].layout) == 1
    print("rnn time major OK")


if __name__ == "__main__":
    main()
