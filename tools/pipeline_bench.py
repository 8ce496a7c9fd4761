"""Input-pipeline throughput benchmark: ImageRecordIter decode+augment
images/sec as a function of preprocess_threads.

The reference decodes recordio with an OMP pool sized by
preprocess_threads (src/io/iter_image_recordio.cc:188-196); this
measures our thread-pool equivalent so the "can the pipeline feed the
chip?" question has a host-side number instead of a guess (what the
chip consumes is the benchmark's `train_samples_per_s`).

Usage:
  python tools/pipeline_bench.py [--rec PATH] [--threads 1,4,8]
      [--procs 2,4] [--image 224] [--num 512] [--batch 64]
      [--seconds 6] [--augment]

Prints one JSON line per thread count:
  {"metric": "input_pipeline_imgs_per_sec", "value": N, "unit": "img/s",
   "threads": T, "image": S, "augment": bool}
and, with --procs, one per process-worker count (preprocess_mode=
"process": GIL-free decode into the shared-memory batch ring):
  {"metric": "input_pipeline_proc_imgs_per_sec", "value": N,
   "unit": "img/s", "procs": P, "image": S, "augment": bool}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_synthetic_rec(path: str, num: int, image: int, seed: int = 0):
    """Pack `num` photo-like JPEGs (smooth gradients + noise compress the
    way real photos do, unlike pure noise) into a recordio file."""
    from mxnet_tpu import recordio as rio

    rng = np.random.RandomState(seed)
    writer = rio.MXRecordIO(path, "w")
    base = np.linspace(0, 255, image)
    grad = np.add.outer(base, base)[:, :, None] / 2.0
    for i in range(num):
        img = (grad + rng.rand(image, image, 3) * 60.0 +
               rng.rand() * 40.0).clip(0, 255).astype(np.uint8)
        writer.write(rio.pack_img(rio.IRHeader(0, float(i % 10), i, 0),
                                  img, quality=90))
    writer.close()


def measure(rec_path: str, image: int, batch: int, threads: int,
            seconds: float, augment: bool, mode: str = None) -> float:
    from mxnet_tpu import io as mio

    kw = {}
    if augment:
        kw.update(rand_crop=True, rand_mirror=True, max_rotate_angle=10,
                  random_h=10, random_s=10, random_l=10)
    if mode is not None:
        kw["preprocess_mode"] = mode
    it = mio.ImageRecordIter(
        path_imgrec=rec_path, data_shape=(3, image, image),
        batch_size=batch, preprocess_threads=threads,
        scale=1.0 / 255.0, **kw)
    # warm the pool + caches with one batch
    next(iter(it))
    it.reset()
    n = 0
    tic = time.time()
    while time.time() - tic < seconds:
        try:
            b = next(it)
        except StopIteration:
            it.reset()
            continue
        # touch the data so lazy work can't be deferred out of the timing
        _ = b.data[0].asnumpy().ravel()[0]
        n += it.batch_size
    rate = n / (time.time() - tic)
    it.close()
    return rate


def measure_cached(rec_path: str, image: int, batch: int, seconds: float,
                   margin: int = 32, threads: int = 4) -> float:
    """Throughput of the pre-decoded cache path (decode once offline,
    then crop/mirror from a uint8 memmap + fused device normalize —
    round-4 verdict #2: the per-epoch JPEG decode can never feed the
    chip from a few cores)."""
    from mxnet_tpu import io_cache

    prefix = rec_path + ".cache"
    io_cache.build_decoded_cache(
        rec_path, prefix, (3, image + margin, image + margin),
        preprocess_threads=threads)
    it = io_cache.CachedImageRecordIter(
        prefix, (3, image, image), batch, shuffle=True, rand_crop=True,
        rand_mirror=True, scale=1.0 / 255.0)
    next(it)
    it.reset()
    n = 0
    tic = time.time()
    while time.time() - tic < seconds:
        try:
            b = next(it)
        except StopIteration:
            it.reset()
            continue
        _ = b.data[0].asnumpy().ravel()[0]
        n += it.batch_size
    return n / (time.time() - tic)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--rec", default=None, help="existing .rec (default: synthesize)")
    p.add_argument("--threads", default="1,%d" % max(2, os.cpu_count() or 1))
    p.add_argument("--procs", default="",
                   help="comma-separated process-worker counts to bench "
                        "(preprocess_mode='process')")
    p.add_argument("--image", type=int, default=224)
    p.add_argument("--num", type=int, default=256)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--seconds", type=float, default=6.0)
    p.add_argument("--augment", action="store_true")
    p.add_argument("--cached", action="store_true",
                   help="also measure the pre-decoded cache path")
    args = p.parse_args(argv)

    tmp = None
    rec = args.rec
    if rec is None:
        tmp = tempfile.mkdtemp(prefix="pipe_bench_")
        rec = os.path.join(tmp, "synth.rec")
        make_synthetic_rec(rec, args.num, args.image)
    results = []
    for t in [int(x) for x in str(args.threads).split(",") if x.strip()]:
        rate = measure(rec, args.image, args.batch, t, args.seconds,
                       args.augment)
        line = {"metric": "input_pipeline_imgs_per_sec",
                "value": round(rate, 1), "unit": "img/s", "threads": t,
                "image": args.image, "augment": bool(args.augment)}
        print(json.dumps(line))
        results.append(line)
    for np_ in [int(x) for x in str(args.procs).split(",") if x.strip()]:
        rate = measure(rec, args.image, args.batch, np_, args.seconds,
                       args.augment, mode="process")
        line = {"metric": "input_pipeline_proc_imgs_per_sec",
                "value": round(rate, 1), "unit": "img/s", "procs": np_,
                "image": args.image, "augment": bool(args.augment)}
        print(json.dumps(line))
        results.append(line)
    if args.cached:
        rate = measure_cached(rec, args.image, args.batch, args.seconds)
        line = {"metric": "input_pipeline_cached_imgs_per_sec",
                "value": round(rate, 1), "unit": "img/s",
                "image": args.image, "augment": True}
        print(json.dumps(line))
        results.append(line)
    return results


if __name__ == "__main__":
    main()
