"""Neural style transfer (reference example/neural-style/run.py +
model_vgg19.py): optimize the INPUT image, not the weights.

This is the one example family that exercises gradient-w.r.t.-data
through the executor: bind with ``args_grad={"data": ...}`` only, call
``backward(head_grads)`` with per-output scaling (style weight / gram
normalizer, content weight), and feed the data gradient to an SGD
optimizer updating the image. A second forward-only executor computes
the total-variation gradient with a fixed Laplacian kernel shared
across channels via SliceChannel/Concat/Convolution — exactly the
reference's ``get_tv_grad_executor`` construction.

Zero-egress adaptation: no pretrained VGG19 download; a fixed-seed
random 3-block VGG-style feature net plays its role (style/gram math is
identical — Gatys-style losses only need a fixed nonlinear feature
extractor). Behavior gate: the style+content objective must drop to
under half its initial value, and image pixels must be what changed.
"""
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx

logging.basicConfig(level=logging.INFO)


def feature_net():
    """3-block conv net; group of (style1, style2, style3, content)."""
    data = mx.sym.Variable("data")
    x = data
    style_layers = []
    channels = [16, 32, 64]
    for b, ch in enumerate(channels, 1):
        x = mx.sym.Convolution(data=x, num_filter=ch, kernel=(3, 3),
                               pad=(1, 1), name="conv%d" % b)
        x = mx.sym.Activation(data=x, act_type="relu", name="relu%d" % b)
        style_layers.append(x)
        if b < len(channels):
            x = mx.sym.Pooling(data=x, kernel=(2, 2), stride=(2, 2),
                               pool_type="avg", name="pool%d" % b)
    content = style_layers[-1]
    return style_layers, content


def gram_symbols(style_layers, input_shape):
    """Gram matrix per style layer via the reference's FullyConnected
    trick: reshape to (C, H*W) then FC(x, weight=x) = x @ x.T."""
    grams, gscale = [], []
    for i, s in enumerate(style_layers):
        _, out_shapes, _ = mx.sym.Group([s]).infer_shape(data=input_shape)
        shape = out_shapes[0]                       # (1, C, H, W)
        c, hw = int(shape[1]), int(np.prod(shape[2:]))
        x = mx.sym.Reshape(s, target_shape=(c, hw))
        grams.append(mx.sym.FullyConnected(data=x, weight=x, no_bias=True,
                                           num_hidden=c))
        gscale.append(float(np.prod(shape[1:]) * shape[1]))
    return grams, gscale


def loss_symbols(grams, content):
    """Per-layer style losses sum((G - target)^2) + content loss."""
    style_losses = []
    for i, g in enumerate(grams):
        target = mx.sym.Variable("target_gram_%d" % i)
        style_losses.append(mx.sym.sum(mx.sym.square(target - g)))
    target_c = mx.sym.Variable("target_content")
    content_loss = mx.sym.sum(mx.sym.square(target_c - content))
    return style_losses, content_loss


def tv_grad_executor(img, tv_weight):
    """Total-variation gradient: depthwise Laplacian via the reference's
    SliceChannel + shared-kernel Convolution + Concat construction."""
    nchannel = img.shape[1]
    simg = mx.sym.Variable("img")
    skernel = mx.sym.Variable("kernel")
    channels = mx.sym.SliceChannel(simg, num_outputs=nchannel)
    out = mx.sym.Concat(*[
        mx.sym.Convolution(data=channels[i], weight=skernel, num_filter=1,
                           kernel=(3, 3), pad=(1, 1), no_bias=True)
        for i in range(nchannel)])
    kernel = mx.nd.array(np.array([[0, -1, 0], [-1, 4, -1], [0, -1, 0]],
                                  dtype=np.float32).reshape(1, 1, 3, 3) / 8.0)
    out = out * tv_weight
    return out.bind(mx.cpu(), args={"img": img, "kernel": kernel})


def main():
    rng = np.random.RandomState(7)
    size = (1, 3, 32, 32)
    content_np = (rng.rand(*size).astype(np.float32) - 0.5) * 2
    style_np = (rng.rand(*size).astype(np.float32) - 0.5) * 2

    style_layers, content_sym = feature_net()
    grams, gscale = gram_symbols(style_layers, size)

    # fixed random "pretrained" weights, shared by every executor
    feat = mx.sym.Group(grams + [content_sym])
    arg_shapes, _, _ = feat.infer_shape(data=size)
    args = {}
    for name, shape in zip(feat.list_arguments(), arg_shapes):
        args[name] = mx.nd.array(
            rng.randn(*shape).astype(np.float32) * (0.3 if "weight" in name
                                                    else 0.0))
    args["data"] = mx.nd.array(content_np)

    # pass 1/2: record style grams of the style image, content features
    # of the content image (forward-only executors)
    exe = feat.bind(mx.cpu(), args=args, grad_req="null")
    args["data"][:] = style_np
    target_grams = [o.asnumpy().copy() for o in exe.forward()[:-1]]
    args["data"][:] = content_np
    target_content = exe.forward()[-1].asnumpy().copy()

    # pass 3: loss graph, bind with gradient ONLY on data
    style_losses, content_loss = loss_symbols(grams, content_sym)
    loss_group = mx.sym.Group(style_losses + [content_loss])
    img = mx.nd.array(rng.uniform(-0.1, 0.1, size).astype(np.float32))
    largs = dict(args)
    largs["data"] = img
    for i, tg in enumerate(target_grams):
        largs["target_gram_%d" % i] = mx.nd.array(tg)
    largs["target_content"] = mx.nd.array(target_content)
    data_grad = mx.nd.zeros(size)
    lexe = loss_group.bind(mx.cpu(), args=largs,
                           args_grad={"data": data_grad}, grad_req="write")

    style_weight, content_weight, tv_weight, lr = 1.0, 10.0, 1e-2, 1e-3
    head_grads = [mx.nd.array(np.full((1,), style_weight / gscale[i],
                                      np.float32))
                  for i in range(len(style_losses))]
    head_grads.append(mx.nd.array(np.full((1,), content_weight, np.float32)))

    tv_exe = tv_grad_executor(img, tv_weight)
    opt = mx.optimizer.SGD(learning_rate=lr, momentum=0.9, wd=0.0,
                           lr_scheduler=mx.lr_scheduler.FactorScheduler(
                               step=40, factor=0.9))
    state = opt.create_state(0, img)

    def objective(outs):
        total = 0.0
        for i in range(len(style_losses)):
            total += float(outs[i].asnumpy().ravel()[0]) \
                * (style_weight / gscale[i])
        total += float(outs[-1].asnumpy().ravel()[0]) * content_weight
        return total

    first = None
    img0 = img.asnumpy().copy()
    clip_norm = float(np.prod(size))
    for epoch in range(80):
        # train forward is lazy here: the fused fwd+bwd materializes the
        # outputs with backward(), so read the loss afterwards
        lexe.forward(is_train=True)
        lexe.backward(head_grads)
        loss = objective(lexe.outputs)
        if first is None:
            first = loss
        g = data_grad.asnumpy()
        gnorm = float(np.linalg.norm(g))
        if gnorm > clip_norm:
            data_grad[:] = g * (clip_norm / gnorm)
        tv = tv_exe.forward()[0]
        opt.update(0, img, data_grad + tv, state)
        if epoch % 10 == 0:
            logging.info("epoch %d style+content loss %.4f", epoch, loss)

    final = objective(lexe.forward())
    moved = float(np.abs(img.asnumpy() - img0).max())
    logging.info("loss %.4f -> %.4f, max pixel change %.4f",
                 first, final, moved)
    assert final < 0.5 * first, (first, final)
    assert moved > 1e-3
    print("neural style OK")


if __name__ == "__main__":
    main()
