"""Kaggle NDSB-1 plankton-classification pipeline (reference
example/kaggle-ndsb1/): the END-TO-END competition workflow —
  1. gen_img_list: walk a class-per-subdirectory image folder, write
     tab-separated .lst files with a stratified train/val split
     (reference gen_img_list.py);
  2. im2rec: pack the lists into recordio (tools/im2rec.py — the
     reference used the same tool);
  3. train: convnet on ImageRecordIter with augmentation
     (reference train_dsb.py over train_model.py);
  4. predict + submission: per-class probability rows indexed by image
     name, header = class names, probabilities summing to 1
     (reference predict_dsb.py + submission_dsb.py gen_sub).

Zero-egress stand-in for the plankton data: generated class-dependent
blob images. Gates: val accuracy and a structurally valid
submission.csv.
"""
import csv
import logging
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx

logging.basicConfig(level=logging.INFO)

CLASSES = ["acantharia", "copepod", "detritus", "shrimp"]
IMG = 24
PER_CLASS = 40


def make_image_folder(root, rng):
    """Class-distinguishable grayscale blobs saved as PNGs."""
    from PIL import Image

    yy, xx = np.mgrid[0:IMG, 0:IMG]
    for ci, cls in enumerate(CLASSES):
        d = os.path.join(root, cls)
        os.makedirs(d)
        for i in range(PER_CLASS):
            cx, cy = rng.randint(8, IMG - 8, 2)
            r = 3 + ci * 1.5
            dist = np.sqrt((xx - cx) ** 2 + (yy - cy) ** 2)
            if ci % 2 == 0:
                img = (dist < r) * 200.0
            else:
                img = ((dist < r) & (dist > r - 2)) * 200.0
            img = img + rng.rand(IMG, IMG) * 40.0
            Image.fromarray(img.clip(0, 255).astype(np.uint8)).save(
                os.path.join(d, "img_%s_%d.png" % (cls, i)))


def gen_img_list(image_folder, out_folder, percent_val=0.25, seed=888):
    """reference gen_img_list.py: enumerate class subdirs, write
    train.lst plus a stratified tr.lst/va.lst split."""
    rng = np.random.RandomState(seed)
    rows_by_class = []
    cnt = 0
    for ci, cls in enumerate(sorted(os.listdir(image_folder))):
        rows = []
        for img in sorted(os.listdir(os.path.join(image_folder, cls))):
            rows.append((cnt, ci, os.path.join(cls, img)))
            cnt += 1
        rows_by_class.append(rows)

    def write(path, rows):
        with open(path, "w") as f:
            w = csv.writer(f, delimiter="\t", lineterminator="\n")
            for r in rows:
                w.writerow(r)

    tr, va = [], []
    for rows in rows_by_class:            # stratified split
        rows = list(rows)
        rng.shuffle(rows)
        k = int(len(rows) * percent_val)
        va.extend(rows[:k])
        tr.extend(rows[k:])
    rng.shuffle(tr)
    write(os.path.join(out_folder, "train.lst"),
          [r for rows in rows_by_class for r in rows])
    write(os.path.join(out_folder, "tr.lst"), tr)
    write(os.path.join(out_folder, "va.lst"), va)


def im2rec(lst, image_root, rec):
    repo = os.path.join(os.path.dirname(__file__), "..", "..")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "im2rec.py"),
         "--list", lst, "--encoding", ".png",
         lst.replace(".lst", ""), image_root + "/"],
        capture_output=True, text=True, env=dict(os.environ))
    assert r.returncode == 0, r.stderr[-800:]
    assert os.path.exists(rec), rec


def get_symbol(num_class):
    """Small conv net in the train_dsb.py spirit."""
    net = mx.sym.Variable("data")
    net = mx.sym.Convolution(net, num_filter=8, kernel=(3, 3), name="c1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = mx.sym.Convolution(net, num_filter=16, kernel=(3, 3), name="c2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=num_class, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def gen_sub(predictions, test_lst_path, submission_path):
    """reference submission_dsb.py gen_sub: header of class names,
    one probability row per image, indexed by file name."""
    images = []
    with open(test_lst_path) as f:
        for line in f:
            if line.strip():
                images.append(line.strip().split("\t")[-1].split("/")[-1])
    with open(submission_path, "w") as f:
        w = csv.writer(f)
        w.writerow(["image"] + CLASSES)
        for img, row in zip(images, predictions):
            w.writerow([img] + ["%.6f" % p for p in row])


def main():
    rng = np.random.RandomState(0)
    tmp = tempfile.mkdtemp(prefix="ndsb1_")
    image_root = os.path.join(tmp, "train")
    os.makedirs(image_root)
    make_image_folder(image_root, rng)

    gen_img_list(image_root, tmp)
    im2rec(os.path.join(tmp, "tr.lst"), image_root,
           os.path.join(tmp, "tr.rec"))
    im2rec(os.path.join(tmp, "va.lst"), image_root,
           os.path.join(tmp, "va.rec"))

    train = mx.io.ImageRecordIter(
        path_imgrec=os.path.join(tmp, "tr.rec"), data_shape=(1, IMG, IMG),
        batch_size=20, shuffle=True, rand_mirror=True,
        scale=1.0 / 255, preprocess_threads=2)
    val = mx.io.ImageRecordIter(
        path_imgrec=os.path.join(tmp, "va.rec"), data_shape=(1, IMG, IMG),
        batch_size=20, scale=1.0 / 255)

    mod = mx.mod.Module(get_symbol(len(CLASSES)), context=mx.cpu())
    mod.fit(train, num_epoch=10, optimizer="adam",
            optimizer_params={"learning_rate": 0.005},
            initializer=mx.initializer.Xavier(magnitude=2.0),
            eval_data=val)
    score = dict(mod.score(val, "acc"))
    acc = next(iter(score.values()))
    logging.info("val accuracy %.3f", acc)
    assert acc > 0.8, score

    # predict + submission over the validation set (reference
    # predict_dsb.py runs the same batch loop over test.rec)
    val.reset()
    probs = []
    for batch in val:
        out = mod.predict_batch(batch) if hasattr(mod, "predict_batch") \
            else None
        if out is None:
            mod.forward(batch, is_train=False)
            out = mod.get_outputs()[0].asnumpy()
        probs.append(out[:out.shape[0] - batch.pad]
                     if batch.pad else out)
    preds = np.concatenate(probs)
    sub = os.path.join(tmp, "submission.csv")
    gen_sub(preds, os.path.join(tmp, "va.lst"), sub)

    with open(sub) as f:
        rows = list(csv.reader(f))
    assert rows[0] == ["image"] + CLASSES
    assert len(rows) - 1 == len(preds)
    body = np.array([[float(x) for x in r[1:]] for r in rows[1:]])
    np.testing.assert_allclose(body.sum(axis=1), 1.0, atol=1e-3)
    print("kaggle ndsb1 OK")


if __name__ == "__main__":
    main()
