"""Composite symbol walkthrough (reference
example/notebooks/composite_symbol.ipynb): build an Inception-style
factory block by composing symbols, inspect arguments/outputs, infer
shapes through the composite, and render the debug description.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx


def conv_factory(data, num_filter, kernel, stride=(1, 1), pad=(0, 0),
                 name=None):
    conv = mx.sym.Convolution(data=data, num_filter=num_filter,
                              kernel=kernel, stride=stride, pad=pad,
                              name="conv_%s" % name)
    bn = mx.sym.BatchNorm(data=conv, name="bn_%s" % name)
    return mx.sym.Activation(data=bn, act_type="relu",
                             name="relu_%s" % name)


def inception_block(data, f1, f3r, f3, f5r, f5, proj, name):
    b1 = conv_factory(data, f1, (1, 1), name="%s_1x1" % name)
    b3 = conv_factory(data, f3r, (1, 1), name="%s_3x3r" % name)
    b3 = conv_factory(b3, f3, (3, 3), pad=(1, 1), name="%s_3x3" % name)
    b5 = conv_factory(data, f5r, (1, 1), name="%s_5x5r" % name)
    b5 = conv_factory(b5, f5, (5, 5), pad=(2, 2), name="%s_5x5" % name)
    bp = mx.sym.Pooling(data=data, kernel=(3, 3), stride=(1, 1),
                        pad=(1, 1), pool_type="max",
                        name="%s_pool" % name)
    bp = conv_factory(bp, proj, (1, 1), name="%s_proj" % name)
    return mx.sym.Concat(b1, b3, b5, bp, name="%s_concat" % name)


data = mx.sym.Variable("data")
blk = inception_block(data, 16, 8, 16, 4, 8, 8, "in3a")
blk = inception_block(blk, 16, 8, 16, 4, 8, 8, "in3b")
pool = mx.sym.Pooling(blk, kernel=(2, 2), global_pool=True,
                      pool_type="avg")
net = mx.sym.FullyConnected(mx.sym.Flatten(pool), num_hidden=10,
                            name="fc")
net = mx.sym.SoftmaxOutput(net, name="softmax")

args = net.list_arguments()
assert "conv_in3a_1x1_weight" in args and "fc_weight" in args
arg_shapes, out_shapes, aux_shapes = net.infer_shape(data=(2, 3, 28, 28))
assert out_shapes[0] == (2, 10)
# two stacked blocks -> concat output feeds the second block
concat_channels = 16 + 16 + 8 + 8
idx = args.index("conv_in3b_1x1_weight")
assert arg_shapes[idx][1] == concat_channels, arg_shapes[idx]
# aux states: one (mean, var) pair per BatchNorm
n_bn = sum(1 for a in net.list_auxiliary_states())
assert n_bn == 2 * 12, n_bn
txt = net.debug_str() if hasattr(net, "debug_str") else str(net)
print("composite symbol OK")
