"""Shared fit() wiring for the image-classification recipes (reference
example/image-classification/train_model.py:1-120): kvstore creation,
per-node logging, checkpoint load/save, dist epoch-size scaling, lr
schedule, clip-gradient, top-k metrics, Speedometer.

train_imagenet.py / train_cifar10.py hand this module their parsed args
plus a data-loader callback, exactly like the reference split.
"""
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


import mxnet_tpu as mx


def fit(args, network, data_loader, batch_end_callback=None):
    # kvstore first: dist tiers must form the collective group before
    # anything touches the accelerator (reference train_model.py:8)
    kv = mx.kv.create(args.kv_store)

    head = "%(asctime)-15s Node[" + str(kv.rank) + "] %(message)s"
    if getattr(args, "log_file", None):
        os.makedirs(args.log_dir, exist_ok=True)
        handler = logging.FileHandler(
            os.path.join(args.log_dir, args.log_file))
        handler.setFormatter(logging.Formatter(head))
        logging.getLogger().addHandler(handler)
        logging.getLogger().setLevel(logging.DEBUG)
    else:
        logging.basicConfig(level=logging.INFO, format=head)
    logging.info("start with arguments %s", args)

    # resume (reference: per-rank prefix so ranks don't clobber)
    model_prefix = args.model_prefix
    if model_prefix is not None and kv.num_workers > 1:
        model_prefix += "-%d" % kv.rank
    model_args = {}
    if getattr(args, "load_epoch", None) is not None:
        assert model_prefix is not None
        net, arg_params, aux_params = mx.model.load_checkpoint(
            model_prefix, args.load_epoch)
        model_args = {"arg_params": arg_params,
                      "aux_params": aux_params,
                      "begin_epoch": args.load_epoch}
        network = net

    save_model_prefix = getattr(args, "save_model_prefix", None)
    if save_model_prefix is not None and kv.num_workers > 1:
        save_model_prefix += "-%d" % kv.rank   # ranks must not clobber
    if save_model_prefix is None:
        save_model_prefix = model_prefix       # already rank-suffixed
    checkpoint = None if save_model_prefix is None \
        else mx.callback.do_checkpoint(save_model_prefix)

    train, val = data_loader(args, kv)

    if getattr(args, "gpus", None):
        devs = [mx.tpu(int(i)) for i in args.gpus.split(",")]
    else:
        devs = mx.cpu()

    epoch_size = args.num_examples // args.batch_size
    if "dist" in args.kv_store:
        epoch_size //= kv.num_workers

    if getattr(args, "lr_factor", 1) < 1:
        model_args["lr_scheduler"] = mx.lr_scheduler.FactorScheduler(
            step=max(int(epoch_size * args.lr_factor_epoch), 1),
            factor=args.lr_factor)
    if getattr(args, "clip_gradient", None) is not None:
        model_args["clip_gradient"] = args.clip_gradient

    model = mx.model.FeedForward(
        ctx=devs,
        symbol=network,
        num_epoch=args.num_epochs,
        learning_rate=args.lr,
        momentum=0.9,
        wd=0.00001,
        initializer=mx.init.Xavier(factor_type="in", magnitude=2.34),
        **model_args)

    eval_metrics = ["accuracy"]
    for top_k in [5]:
        eval_metrics.append(mx.metric.create("top_k_accuracy",
                                             top_k=top_k))

    callbacks = list(batch_end_callback or [])
    callbacks.append(mx.callback.Speedometer(args.batch_size, 50))

    model.fit(X=train, eval_data=val, eval_metric=eval_metrics,
              kvstore=kv, batch_end_callback=callbacks,
              epoch_end_callback=checkpoint)
    return model
