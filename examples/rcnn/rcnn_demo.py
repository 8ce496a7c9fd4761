#!/usr/bin/env python
"""Faster R-CNN building blocks demo (reference example/rcnn): ROIPooling
op + a Proposal layer implemented as a frontend CustomOp — the two pieces
BASELINE.md names as the rcnn target."""
import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import operator as mop
from mxnet_tpu import symbol as sym


@mop.register("proposal")
class ProposalProp(mop.CustomOpProp):
    """Generate top-N box proposals from objectness scores + anchor deltas
    (simplified reference rcnn/symbol/proposal.py)."""

    def __init__(self, feat_stride="16", rpn_post_nms_top_n="8", **kwargs):
        super().__init__(need_top_grad=False)
        self.feat_stride = int(feat_stride)
        self.top_n = int(rpn_post_nms_top_n)

    def list_arguments(self):
        return ["cls_prob", "bbox_pred", "im_info"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        return in_shape, [[self.top_n, 5]], []

    def create_operator(self, ctx, in_shapes, in_dtypes):
        top_n = self.top_n
        stride = self.feat_stride

        class Proposal(mop.CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                scores = in_data[0].asnumpy()       # (N, A, H, W)
                deltas = in_data[1].asnumpy()       # (N, A*4, H, W)
                im_info = in_data[2].asnumpy()      # (N, 3)
                n, a, h, w = scores.shape
                ys, xs = np.meshgrid(np.arange(h), np.arange(w),
                                     indexing="ij")
                cx = (xs * stride + stride / 2).ravel()
                cy = (ys * stride + stride / 2).ravel()
                flat = scores[0].reshape(a, -1)
                order = np.argsort(flat.max(axis=0))[::-1][:top_n]
                size = stride * 1.5
                boxes = np.zeros((top_n, 5), dtype=np.float32)
                for i, idx in enumerate(order):
                    boxes[i] = [0, max(cx[idx] - size, 0),
                                max(cy[idx] - size, 0),
                                min(cx[idx] + size, im_info[0, 1]),
                                min(cy[idx] + size, im_info[0, 0])]
                self.assign(out_data[0], req[0], boxes)

            def backward(self, req, out_grad, in_data, out_data, in_grad,
                         aux):
                for g in in_grad:
                    g[:] = 0
        return Proposal()


def main():
    logging.basicConfig(level=logging.INFO)
    # toy backbone -> rpn -> proposal -> roi pooling -> head
    data = sym.Variable("data")
    conv = sym.Convolution(data=data, kernel=(3, 3), num_filter=8,
                           pad=(1, 1), name="backbone")
    relu = sym.Activation(conv, act_type="relu")
    rpn_cls = sym.Convolution(data=relu, kernel=(1, 1), num_filter=4,
                              name="rpn_cls")
    rpn_bbox = sym.Convolution(data=relu, kernel=(1, 1), num_filter=16,
                               name="rpn_bbox")
    im_info = sym.Variable("im_info")
    rois = sym.Custom(cls_prob=rpn_cls, bbox_pred=rpn_bbox, im_info=im_info,
                      op_type="proposal", feat_stride="4",
                      rpn_post_nms_top_n="8", name="proposal")
    pooled = sym.ROIPooling(data=relu, rois=rois, pooled_size=(3, 3),
                            spatial_scale=0.25, name="roi_pool")
    flat = sym.Flatten(data=pooled)
    cls = sym.FullyConnected(data=flat, num_hidden=4, name="cls_head")
    out = sym.SoftmaxActivation(cls, name="cls_prob")

    rng = np.random.RandomState(0)
    shapes = {"data": (1, 3, 32, 32), "im_info": (1, 3)}
    arg_shapes, out_shapes, _ = out.infer_shape(**shapes)
    args = {}
    for name, shape in zip(out.list_arguments(), arg_shapes):
        args[name] = mx.nd.array(rng.randn(*shape).astype(np.float32) * 0.1)
    args["im_info"][:] = np.array([[32, 32, 1.0]], dtype=np.float32)
    ex = out.bind(mx.cpu(), args, grad_req="null")
    result = ex.forward()[0].asnumpy()
    print("rcnn head output:", result.shape)  # (8 rois, 4 classes)
    assert result.shape == (8, 4)
    np.testing.assert_allclose(result.sum(axis=1), np.ones(8), rtol=1e-5)
    print("Faster R-CNN pipeline (Proposal CustomOp + ROIPooling) OK")


if __name__ == "__main__":
    main()
