"""Speech acoustic-model demo (reference example/speech-demo/):
frame-level classification with an explicitly unrolled projection LSTM
(lstm_proj.py) trained by the speechSGD optimizer (speechSGD.py).

What this family uniquely exercises:
  * LSTMP — LSTM with a recurrent PROJECTION layer: the hidden state
    fed back into the recurrence is a lower-dimensional linear
    projection of the cell output (Sak et al.; reference
    ``lstm_proj.py:16-58``), plus peephole connections implemented as
    broadcast_mul with (1, H)-shaped bias variables;
  * an unrolled per-timestep symbol graph (node-per-timestep, shared
    weight variables — the reference's pre-scan RNN style) rather than
    the fused RNN op;
  * a custom optimizer registered from user code: speechSGD's momentum
    rule ``mom = momentum*mom - lr*(1-momentum)*(grad + wd*w)``
    (reference ``speechSGD.py:76-110``), exercising the optimizer
    registry extension path.

Zero-egress stand-in for Kaldi features: synthetic utterances whose
frame class depends on a sliding window of the input, so temporal
context (the LSTM memory) is required to beat a frame-wise classifier.
"""
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx

logging.basicConfig(level=logging.INFO)

SEQ_LEN = 10
NFEAT = 6
NHID = 24
NPROJ = 12
NCLASS = 3
BATCH = 16


@mx.optimizer.register
class speechSGD(mx.optimizer.Optimizer):
    """The reference's speech-recipe momentum rule (speechSGD.py):
    the gradient term is scaled by (1 - momentum)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return mx.nd.zeros(weight.shape, ctx=weight.context)

    def update(self, index, weight, grad, state):
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        self._update_count(index)
        g = grad * self.rescale_grad
        if self.clip_gradient is not None:
            g = mx.nd.clip(g, -self.clip_gradient, self.clip_gradient)
        if state is not None:
            state[:] = self.momentum * state \
                - lr * (1.0 - self.momentum) * (g + wd * weight)
            weight[:] = weight + state
        else:
            weight[:] = weight - lr * (g + wd * weight)


def lstmp_cell(num_hidden, num_proj, indata, prev_c, prev_h, params, t):
    """One unrolled LSTMP step (reference lstm_proj.py lstm()):
    peephole terms via broadcast_mul of (1, H) biases with the cell."""
    i2h = mx.sym.FullyConnected(data=indata, weight=params["i2h_weight"],
                                bias=params["i2h_bias"],
                                num_hidden=num_hidden * 4,
                                name="t%d_i2h" % t)
    h2h = mx.sym.FullyConnected(data=prev_h, weight=params["h2h_weight"],
                                no_bias=True, num_hidden=num_hidden * 4,
                                name="t%d_h2h" % t)
    gates = mx.sym.SliceChannel(i2h + h2h, num_outputs=4,
                                name="t%d_slice" % t)
    in_gate = mx.sym.Activation(
        mx.sym.broadcast_mul(params["c2i_bias"], prev_c) + gates[0],
        act_type="sigmoid")
    in_transform = mx.sym.Activation(gates[1], act_type="tanh")
    forget_gate = mx.sym.Activation(
        mx.sym.broadcast_mul(params["c2f_bias"], prev_c) + gates[2],
        act_type="sigmoid")
    next_c = forget_gate * prev_c + in_gate * in_transform
    out_gate = mx.sym.Activation(
        mx.sym.broadcast_mul(params["c2o_bias"], next_c) + gates[3],
        act_type="sigmoid")
    next_h = out_gate * mx.sym.Activation(next_c, act_type="tanh")
    # the projection: what recurs is W_p * h, dim num_proj < num_hidden
    proj_h = mx.sym.FullyConnected(data=next_h,
                                   weight=params["ph2h_weight"],
                                   no_bias=True, num_hidden=num_proj,
                                   name="t%d_ph2h" % t)
    return next_c, proj_h


def lstmp_unroll(seq_len, num_hidden, num_proj, num_label):
    params = {
        "i2h_weight": mx.sym.Variable("l0_i2h_weight"),
        "i2h_bias": mx.sym.Variable("l0_i2h_bias"),
        "h2h_weight": mx.sym.Variable("l0_h2h_weight"),
        "ph2h_weight": mx.sym.Variable("l0_ph2h_weight"),
        "c2i_bias": mx.sym.Variable("l0_c2i_bias", shape=(1, num_hidden)),
        "c2f_bias": mx.sym.Variable("l0_c2f_bias", shape=(1, num_hidden)),
        "c2o_bias": mx.sym.Variable("l0_c2o_bias", shape=(1, num_hidden)),
    }
    cls_weight = mx.sym.Variable("cls_weight")
    cls_bias = mx.sym.Variable("cls_bias")
    data = mx.sym.Variable("data")          # (batch, T, feat)
    label = mx.sym.Variable("softmax_label")  # (batch, T)
    frames = mx.sym.SliceChannel(data, num_outputs=seq_len, axis=1,
                                 squeeze_axis=True, name="frames")
    c = mx.sym.Variable("init_c")
    h = mx.sym.Variable("init_h")
    outs = []
    for t in range(seq_len):
        c, h = lstmp_cell(num_hidden, num_proj, frames[t], c, h, params, t)
        fc = mx.sym.FullyConnected(data=h, weight=cls_weight,
                                   bias=cls_bias, num_hidden=num_label,
                                   name="t%d_cls" % t)
        outs.append(fc)
    pred = mx.sym.Concat(*[mx.sym.Reshape(o, shape=(-1, 1, num_label))
                           for o in outs], dim=1)   # (batch, T, nclass)
    return mx.sym.SoftmaxOutput(data=pred, label=label,
                                preserve_shape=True, name="softmax")


def make_data(rng, n):
    """Class of frame t = sign pattern of feature-sums over a 3-frame
    window: needs memory, a frame-wise classifier caps at ~chance."""
    X = rng.randn(n, SEQ_LEN, NFEAT).astype(np.float32)
    s = X.sum(axis=2)
    ctx = np.stack([np.roll(s, 1, axis=1), s,
                    np.roll(s, 2, axis=1)], axis=0)
    y = ((ctx[0] > 0).astype(int) + (ctx[2] > 0).astype(int))
    y[:, :2] = 0      # frames without full context get class 0
    return X, y.astype(np.float32)


def main():
    rng = np.random.RandomState(3)
    X, y = make_data(rng, 480)
    Xv, yv = make_data(rng, 96)

    net = lstmp_unroll(SEQ_LEN, NHID, NPROJ, NCLASS)

    class UttIter(mx.io.DataIter):
        def __init__(self, X, y):
            super().__init__()
            self.X, self.y = X, y
            self.batch_size = BATCH
            self.cursor = -BATCH

        @property
        def provide_data(self):
            return [mx.io.DataDesc("data", (BATCH, SEQ_LEN, NFEAT)),
                    mx.io.DataDesc("init_c", (BATCH, NHID)),
                    mx.io.DataDesc("init_h", (BATCH, NPROJ))]

        @property
        def provide_label(self):
            return [mx.io.DataDesc("softmax_label", (BATCH, SEQ_LEN))]

        def reset(self):
            self.cursor = -BATCH

        def iter_next(self):
            self.cursor += BATCH
            return self.cursor + BATCH <= len(self.X)

        def getdata(self):
            sl = slice(self.cursor, self.cursor + BATCH)
            return [mx.nd.array(self.X[sl]),
                    mx.nd.zeros((BATCH, NHID)),
                    mx.nd.zeros((BATCH, NPROJ))]

        def getlabel(self):
            sl = slice(self.cursor, self.cursor + BATCH)
            return [mx.nd.array(self.y[sl])]

    def frame_acc(label, pred):
        lab = label.reshape(-1).astype(int)
        p = pred.reshape(-1, NCLASS)
        return float((p.argmax(axis=1) == lab).mean())

    mod = mx.mod.Module(net,
                        data_names=["data", "init_c", "init_h"],
                        label_names=["softmax_label"], context=mx.cpu())
    mod.fit(UttIter(X, y), num_epoch=8,
            eval_metric=mx.metric.np_metric(frame_acc, name="frame_acc"),
            initializer=mx.initializer.Xavier(magnitude=2.0),
            optimizer="speechsgd",
            optimizer_params={"learning_rate": 0.06, "momentum": 0.9})

    score = dict(mod.score(UttIter(Xv, yv),
                           mx.metric.np_metric(frame_acc,
                                               name="frame_acc")))
    acc = next(iter(score.values()))
    logging.info("frame accuracy %.3f (chance ~0.4)", acc)
    assert acc > 0.8, score
    print("speech demo OK")


if __name__ == "__main__":
    main()
