#!/usr/bin/env python
"""Module API walkthrough (reference example/module + python-howto):
the manual bind/init/forward/backward/update loop, fit(), checkpointing,
and BucketingModule — the intermediate-level API tour the reference's
notebooks gave.
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx


def build():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def main(seed=0):
    rng = np.random.RandomState(seed)
    n, d = 512, 10
    y = rng.randint(0, 2, n).astype(np.float32)
    X = (rng.randn(n, d) + y[:, None] * 1.8).astype(np.float32)
    net = build()

    # --- 1. the manual loop -------------------------------------------
    mod = mx.mod.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=64, shuffle=True)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.2})
    metric = mx.metric.create("acc")
    for epoch in range(5):
        it.reset()
        metric.reset()
        for batch in it:
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
            mod.update_metric(metric, batch.label)
    name, acc = metric.get()
    print("manual loop %s: %.3f" % (name, acc))
    assert acc > 0.9, acc

    # --- 2. fit() + checkpoint ----------------------------------------
    prefix = os.path.join(tempfile.mkdtemp(), "howto")
    mod2 = mx.mod.Module(net, context=mx.cpu())
    it.reset()
    mod2.fit(it, num_epoch=3,
             optimizer_params={"learning_rate": 0.2},
             epoch_end_callback=mx.callback.do_checkpoint(prefix))
    sym_loaded, arg_params, aux_params = \
        mx.model.load_checkpoint(prefix, 3)
    assert sym_loaded.tojson() == net.tojson()
    assert set(arg_params) == {"fc1_weight", "fc1_bias", "fc2_weight",
                               "fc2_bias"}
    print("fit + checkpoint OK (%s-0003.params)" % prefix)

    # --- 3. predict with loaded params --------------------------------
    mod3 = mx.mod.Module(net, context=mx.cpu())
    pit = mx.io.NDArrayIter(X, y, batch_size=64)
    mod3.bind(data_shapes=pit.provide_data, for_training=False)
    mod3.set_params(arg_params, aux_params)
    preds = mod3.predict(pit)
    acc = (preds.asnumpy().argmax(axis=1) == y).mean()
    print("restored-module accuracy: %.3f" % acc)
    assert acc > 0.9, acc
    print("module howto OK")


if __name__ == "__main__":
    main()
