#!/usr/bin/env python
"""FCN semantic segmentation (reference example/fcn-xs): a conv
encoder, a 1x1 class head, and a Deconvolution (transposed conv)
upsampling path with Crop to the input geometry — per-pixel
SoftmaxOutput with multi_output, trained on a synthetic
blob-segmentation task.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx

SIZE = 16
CLASSES = 2


def build_net():
    data = mx.sym.Variable("data")                        # (N,1,16,16)
    c1 = mx.sym.Convolution(data, kernel=(3, 3), pad=(1, 1),
                            num_filter=8, name="c1")
    c1 = mx.sym.Activation(c1, act_type="relu")
    p1 = mx.sym.Pooling(c1, kernel=(2, 2), stride=(2, 2),
                        pool_type="max")                  # (N,8,8,8)
    c2 = mx.sym.Convolution(p1, kernel=(3, 3), pad=(1, 1),
                            num_filter=16, name="c2")
    c2 = mx.sym.Activation(c2, act_type="relu")
    score = mx.sym.Convolution(c2, kernel=(1, 1), num_filter=CLASSES,
                               name="score")              # (N,C,8,8)
    up = mx.sym.Deconvolution(score, kernel=(4, 4), stride=(2, 2),
                              pad=(1, 1), num_filter=CLASSES,
                              name="up")                  # (N,C,16,16)
    up = mx.sym.Crop(up, data, name="crop")               # FCN crop-to-ref
    return mx.sym.SoftmaxOutput(up, multi_output=True, name="softmax")


def make_data(rng, n):
    """Images with a bright square blob; label = blob mask."""
    X = rng.rand(n, 1, SIZE, SIZE).astype(np.float32) * 0.3
    Y = np.zeros((n, SIZE, SIZE), np.float32)
    for i in range(n):
        r, c = rng.randint(1, SIZE - 9, 2)
        h, w = rng.randint(6, 9, 2)
        X[i, 0, r:r + h, c:c + w] += 0.7
        Y[i, r:r + h, c:c + w] = 1.0
    return X, Y


def main(seed=0):
    rng = np.random.RandomState(seed)
    X, Y = make_data(rng, 256)
    net = build_net()
    it = mx.io.NDArrayIter({"data": X}, {"softmax_label": Y},
                           batch_size=32, shuffle=True)
    model = mx.model.FeedForward.create(
        net, X=it, num_epoch=25, optimizer="adam", learning_rate=2e-2,
        ctx=mx.cpu())
    pred = model.predict(mx.io.NDArrayIter({"data": X}, batch_size=32))
    mask = pred.argmax(axis=1)                            # (N,16,16)
    iou_num = np.logical_and(mask == 1, Y == 1).sum()
    iou_den = np.logical_or(mask == 1, Y == 1).sum()
    iou = iou_num / max(iou_den, 1)
    print("blob IoU: %.3f" % iou)
    assert iou > 0.8, iou
    print("FCN OK")


if __name__ == "__main__":
    main()
