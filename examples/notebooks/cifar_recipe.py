"""CIFAR training recipe (reference example/notebooks/cifar10-recipe.ipynb
+ cifar-100.ipynb): the full training workflow in one place —
ImageRecordIter data with augmentation, a conv factory net, an lr
FactorScheduler, per-epoch do_checkpoint callbacks, RESUME from a
saved epoch, and final scoring.

Zero-egress stand-in for CIFAR: synthetic 3x28x28 class-blob images
packed into recordio (the pipeline is identical).
"""
import glob
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import recordio as rio

NCLASS = 3
IMG = 28


def make_rec(path, n, seed):
    rng = np.random.RandomState(seed)
    w = rio.MXRecordIO(path, "w")
    yy, xx = np.mgrid[0:IMG, 0:IMG]
    for i in range(n):
        c = i % NCLASS
        # class encoded in the blob's VERTICAL position: rand_mirror
        # flips x, so the label must not live on the x axis
        cx, cy = 14, 6 + 8 * c
        img = (((xx - cx) ** 2 + (yy - cy) ** 2) < 16) * 180.0
        img = (img[:, :, None] + rng.rand(IMG, IMG, 3) * 50).clip(0, 255)
        w.write(rio.pack_img(rio.IRHeader(0, float(c), i, 0),
                             img.astype(np.uint8), quality=95))
    w.close()


def conv_factory(data, num_filter, name):
    c = mx.sym.Convolution(data, num_filter=num_filter, kernel=(3, 3),
                           pad=(1, 1), name="conv_%s" % name)
    bn = mx.sym.BatchNorm(c, name="bn_%s" % name)
    return mx.sym.Activation(bn, act_type="relu", name="relu_%s" % name)


def build_net():
    net = mx.sym.Variable("data")
    net = conv_factory(net, 8, "a")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = conv_factory(net, 16, "b")
    net = mx.sym.Pooling(net, kernel=(2, 2), global_pool=True,
                         pool_type="avg")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=NCLASS,
                                name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def main():
    tmp = tempfile.mkdtemp(prefix="cifar_recipe_")
    make_rec(os.path.join(tmp, "train.rec"), 192, seed=0)
    make_rec(os.path.join(tmp, "val.rec"), 48, seed=1)

    def iters():
        train = mx.io.ImageRecordIter(
            path_imgrec=os.path.join(tmp, "train.rec"),
            data_shape=(3, IMG, IMG), batch_size=24, shuffle=True,
            rand_mirror=True, scale=1.0 / 255, preprocess_threads=2)
        val = mx.io.ImageRecordIter(
            path_imgrec=os.path.join(tmp, "val.rec"),
            data_shape=(3, IMG, IMG), batch_size=24, scale=1.0 / 255)
        return train, val

    prefix = os.path.join(tmp, "cifar")
    train, val = iters()
    model = mx.model.FeedForward(
        build_net(), ctx=mx.cpu(), num_epoch=6,
        optimizer="adam", learning_rate=0.01,
        initializer=mx.initializer.Xavier(),
        lr_scheduler=mx.lr_scheduler.FactorScheduler(step=16, factor=0.9))
    model.fit(X=train, eval_data=val,
              epoch_end_callback=mx.callback.do_checkpoint(prefix),
              batch_end_callback=mx.callback.Speedometer(24, 4))
    assert glob.glob(prefix + "-symbol.json"), "no symbol checkpoint"
    assert glob.glob(prefix + "-000*.params"), "no param checkpoints"

    # resume from epoch 3 and continue to 10 (the notebook's resume cell)
    resumed = mx.model.FeedForward.load(prefix, 3, ctx=mx.cpu(),
                                        num_epoch=10, optimizer="adam",
                                        learning_rate=0.005)
    train, val = iters()
    resumed.fit(X=train, eval_data=val)   # resumes at begin_epoch=3 from load()

    train, val = iters()
    acc = resumed.score(val)
    print("val accuracy after resume: %.3f" % acc)
    assert acc > 0.9, acc
    print("cifar recipe OK")


if __name__ == "__main__":
    main()
