#!/usr/bin/env python
"""Train an image classifier on ImageNet recordio files (reference
example/image-classification/train_imagenet.py:1-87 — the reference's
north-star training recipe).

Data: train.rec / val.rec built by tools/im2rec.py. Each worker reads
its own shard (num_parts=kv.num_workers, part_index=kv.rank), exactly
the reference's DP input sharding; kvstore tpu_sync runs the in-step
GSPMD all-reduce on one host, dist_sync spans hosts via
tools/launch.py.

Single chip:
    python train_imagenet.py --data-dir /data/imagenet --gpus 0
Multi-host DP:
    python tools/launch.py -n 4 --launcher ssh -H hosts.txt \
        python train_imagenet.py --data-dir /data/imagenet \
        --kv-store dist_sync
"""
import argparse
import importlib
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


import mxnet_tpu as mx
import train_model

# -n / -s stay reserved for the distributed launcher (reference note)
parser = argparse.ArgumentParser(
    description="train an image classifier on imagenet")
parser.add_argument("--network", default="inception-bn",
                    choices=["alexnet", "vgg", "googlenet",
                             "inception-bn", "inception-v3", "resnet"],
                    help="the cnn to use")
parser.add_argument("--data-dir", required=True,
                    help="directory holding train.rec / val.rec")
parser.add_argument("--model-prefix", default=None,
                    help="prefix of the checkpoint to load")
parser.add_argument("--save-model-prefix", default=None,
                    help="prefix of the checkpoint to save")
parser.add_argument("--lr", type=float, default=0.01)
parser.add_argument("--lr-factor", type=float, default=1,
                    help="multiply lr by this every lr-factor-epoch")
parser.add_argument("--lr-factor-epoch", type=float, default=1)
parser.add_argument("--clip-gradient", type=float, default=5.0)
parser.add_argument("--num-epochs", type=int, default=20)
parser.add_argument("--load-epoch", type=int, default=None)
parser.add_argument("--batch-size", type=int, default=32)
parser.add_argument("--gpus", default=None,
                    help="accelerator ids, e.g. '0' (TPU chips here)")
parser.add_argument("--kv-store", default="local",
                    help="local | tpu_sync | dist_sync | dist_async")
parser.add_argument("--num-examples", type=int, default=1281167)
parser.add_argument("--num-classes", type=int, default=1000)
parser.add_argument("--log-file", default=None)
parser.add_argument("--log-dir", default="/tmp/")
parser.add_argument("--train-dataset", default="train.rec")
parser.add_argument("--val-dataset", default="val.rec")
parser.add_argument("--data-shape", type=int, default=224,
                    help="input image edge length")
parser.add_argument("--preprocess-threads", type=int, default=4,
                    help="decode pool size (feed-the-chip knob)")
parser.add_argument("--use-cache", action="store_true",
                    help="decode each .rec ONCE into a uint8 memmap "
                         "cache next to it, then feed training from the "
                         "cache with crop/mirror/normalize fused on "
                         "device — sustains TPU-rate input from one "
                         "host core (docs/performance.md); per-epoch "
                         "JPEG decode needs ~28 cores at 224px")
parser.add_argument("--cache-margin", type=int, default=32,
                    help="stored-image margin above the crop size "
                         "(store 256 for 224 crops)")
args = parser.parse_args()


def get_net(name, num_classes):
    from mxnet_tpu import models

    if name == "resnet":
        return models.get_resnet50(num_classes=num_classes)
    if name == "inception-bn":
        return models.get_inception_bn(num_classes=num_classes)
    builders = {"alexnet": models.get_alexnet, "vgg": models.get_vgg,
                "googlenet": models.get_googlenet,
                "inception-v3": models.get_inception_v3}
    return builders[name](num_classes)


def get_iterator(args, kv):
    data_shape = (3, args.data_shape, args.data_shape)
    if args.use_cache:
        return get_cached_iterator(args, kv, data_shape)
    train = mx.io.ImageRecordIter(
        path_imgrec=os.path.join(args.data_dir, args.train_dataset),
        mean_r=123.68, mean_g=116.779, mean_b=103.939,
        data_shape=data_shape,
        batch_size=args.batch_size,
        rand_crop=True,
        rand_mirror=True,
        shuffle=True,
        preprocess_threads=args.preprocess_threads,
        num_parts=kv.num_workers,
        part_index=kv.rank)
    val = mx.io.ImageRecordIter(
        path_imgrec=os.path.join(args.data_dir, args.val_dataset),
        mean_r=123.68, mean_g=116.779, mean_b=103.939,
        rand_crop=False,
        rand_mirror=False,
        data_shape=data_shape,
        batch_size=args.batch_size,
        preprocess_threads=args.preprocess_threads,
        num_parts=kv.num_workers,
        part_index=kv.rank)
    return train, val


def get_cached_iterator(args, kv, data_shape):
    """The cache-fed input path (mxnet_tpu.io_cache): decode each .rec
    once into a memmapped uint8 store, then feed every epoch from the
    cache with the augmentation arithmetic fused on device. Exactly ONE
    rank builds (O_EXCL lockfile in the shared data dir); the others
    wait for the finished cache, and a regenerated .rec invalidates it
    (size/mtime fingerprint in the meta)."""
    from mxnet_tpu import io_cache

    store = args.data_shape + args.cache_margin
    iters = []
    for dataset, train_aug in ((args.train_dataset, True),
                               (args.val_dataset, False)):
        rec = os.path.join(args.data_dir, dataset)
        prefix = rec + ".cache"
        io_cache.build_decoded_cache(
            rec, prefix, (3, store, store),
            preprocess_threads=args.preprocess_threads)
        iters.append(io_cache.CachedImageRecordIter(
            prefix, data_shape, args.batch_size,
            shuffle=train_aug, rand_crop=train_aug,
            rand_mirror=train_aug, device_augment=True,
            mean_r=123.68, mean_g=116.779, mean_b=103.939,
            num_parts=kv.num_workers, part_index=kv.rank))
    return iters[0], iters[1]


net = get_net(args.network, args.num_classes)
train_model.fit(args, net, get_iterator)
print("train imagenet OK")
