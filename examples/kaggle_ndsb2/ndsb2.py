"""Kaggle NDSB-2 cardiac-volume pipeline (reference
example/kaggle-ndsb2/Train.py): predict a cumulative distribution
P(volume <= v) per case and score with CRPS.

What this family uniquely exercises:
  * frame-DIFFERENCE input built symbolically: SliceChannel over the
    frame axis, pairwise subtraction, Concat (reference
    ``Train.py:16-24`` — in-graph preprocessing, not host-side);
  * LogisticRegressionOutput with a VECTOR label per sample (the
    600-bin CDF target; here 40 bins), the sigmoid-regression path;
  * CDF label encoding ``(x < arange(bins))`` (reference
    ``encode_label``) and the CRPS metric with monotonic rectification
    of the predicted CDF (reference ``Train.py:40-50``).

Synthetic stand-in: "volume" is the number of active pixels in a
moving blob across frames; the CDF target thresholds it. Gates: CRPS
well under the 0.25 chance level and a monotone submission.
"""
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx

logging.basicConfig(level=logging.INFO)

FRAMES = 6
IMG = 12
BINS = 40


def get_net():
    source = mx.sym.Variable("data")
    source = (source - 128.0) * (1.0 / 128.0)
    frames = mx.sym.SliceChannel(source, num_outputs=FRAMES)
    diffs = [frames[i + 1] - frames[i] for i in range(FRAMES - 1)]
    source = mx.sym.Concat(*diffs)
    net = mx.sym.Convolution(source, kernel=(3, 3), num_filter=8)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, pool_type="max", kernel=(2, 2),
                         stride=(2, 2))
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(data=net, num_hidden=BINS)
    return mx.sym.LogisticRegressionOutput(data=net, name="softmax")


def CRPS(label, pred):
    """Continuous ranked probability score with the reference's
    monotonic rectification of the predicted CDF."""
    pred = pred.copy()
    for j in range(pred.shape[1] - 1):
        pred[:, j + 1] = np.maximum(pred[:, j + 1], pred[:, j])
    return float(np.sum(np.square(label - pred)) / label.size)


def encode_label(volumes):
    """CDF target: bin b is 1 iff volume < b (reference encode_label)."""
    return np.array([(v < np.arange(BINS)) for v in volumes],
                    dtype=np.float32)


def make_data(rng, n):
    X = np.zeros((n, FRAMES, IMG, IMG), dtype=np.float32)
    vol = np.zeros(n)
    yy, xx = np.mgrid[0:IMG, 0:IMG]
    for i in range(n):
        r = rng.uniform(1.5, 4.5)
        for t in range(FRAMES):
            cx = 4 + 2 * np.sin(t / 2.0)
            cy = 4 + 2 * np.cos(t / 2.0)
            mask = ((xx - cx) ** 2 + (yy - cy) ** 2) < r ** 2
            X[i, t] = mask * 200.0 + rng.rand(IMG, IMG) * 20.0
        vol[i] = (np.pi * r * r) * BINS / 80.0   # scaled to bin range
    return X, encode_label(vol)


def main():
    rng = np.random.RandomState(0)
    X, y = make_data(rng, 320)
    Xv, yv = make_data(rng, 64)

    it = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=True,
                           label_name="softmax_label")
    vit = mx.io.NDArrayIter(Xv, yv, batch_size=32,
                            label_name="softmax_label")

    mod = mx.mod.Module(get_net(), context=mx.cpu())
    mod.fit(it, num_epoch=12, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.initializer.Xavier(magnitude=2.0),
            eval_metric=mx.metric.np_metric(CRPS, name="CRPS"))

    vit.reset()
    preds = []
    for batch in vit:
        mod.forward(batch, is_train=False)
        preds.append(mod.get_outputs()[0].asnumpy())
    pred = np.concatenate(preds)[:len(Xv)]
    score = CRPS(yv, pred)
    logging.info("validation CRPS %.4f (chance ~0.25)", score)
    assert score < 0.05, score

    # submission_helper: rectified monotone CDF rows in [0, 1]
    mono = pred.copy()
    for j in range(BINS - 1):
        mono[:, j + 1] = np.maximum(mono[:, j + 1], mono[:, j])
    assert (np.diff(mono, axis=1) >= 0).all()
    assert mono.min() >= 0.0 and mono.max() <= 1.0
    print("kaggle ndsb2 OK")


if __name__ == "__main__":
    main()
