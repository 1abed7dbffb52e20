"""Image classification with a pretrained model (reference
example/imageclassification/ImagePredictor.scala + MlUtils.scala):
read an image folder, run the preprocessing pipeline, and predict
classes with the model broadcast once — here the compiled (optionally
sharded) predictor forward.

Usage:
    JAX_PLATFORMS=cpu python -m bigdl_tpu.examples.image_predictor \
        --model lenet.bin --folder images/ [--distributed]
"""
from __future__ import annotations

import argparse

import numpy as np


def predict_folder(model, folder: str, image_size: int = 28,
                   batch_size: int = 32, mesh=None):
    """ImagePredictor.predict: folder -> pipeline -> predictClass."""
    from ..dataset import Sample, array, image_folder

    pairs = image_folder(folder, scale_to=image_size)
    samples = [Sample((bgr.astype(np.float32) / 255.0)
                      .transpose(2, 0, 1)[:, :image_size, :image_size],
                      label) for bgr, label in pairs]
    classes = model.predict_class(array(samples), batch_size=batch_size,
                                  mesh=mesh)
    return classes, samples


def demo():
    """Self-contained run: trains a small conv net on bundled digit
    scans, writes held-out digits to a class-per-subdir PNG tree, and
    predicts them back through the REAL folder pipeline
    (``image_folder`` → Samples → ``predict_folder``)."""
    import os
    import tempfile

    from PIL import Image

    from .. import nn
    from ..dataset import Sample
    from ..dataset.dataset import array
    from ..optim import SGD, LocalOptimizer, max_epoch
    from sklearn.datasets import load_digits

    d = load_digits()
    imgs = d.images.astype(np.float32) / 16.0     # (N, 8, 8) in [0, 1]
    labels = d.target
    rng = np.random.RandomState(0)
    order = rng.permutation(len(imgs))
    imgs, labels = imgs[order], labels[order]

    # train a conv net on the (3, 8, 8) contract predict_folder produces
    train = [Sample(np.repeat(imgs[i][None], 3, axis=0),
                    float(labels[i]) + 1) for i in range(1500)]
    model = nn.Sequential(
        nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1), nn.ReLU(),
        nn.Reshape([8 * 8 * 8]), nn.Linear(512, 10), nn.LogSoftMax())
    opt = LocalOptimizer(model, array(train), nn.ClassNLLCriterion(),
                         batch_size=64)
    opt.set_optim_method(SGD(learning_rate=0.1))
    opt.set_end_when(max_epoch(10))
    opt.optimize()

    # write held-out digits as a <class>/<image>.png tree
    folder = tempfile.mkdtemp(prefix="bigdl_imgpred_")
    truth = []
    for i in range(1500, 1564):
        cls_dir = os.path.join(folder, f"{labels[i]}")
        os.makedirs(cls_dir, exist_ok=True)
        grey = (imgs[i] * 255).astype(np.uint8)
        Image.fromarray(grey).convert("RGB").save(
            os.path.join(cls_dir, f"{i}.png"))

    classes, samples = predict_folder(model, folder, image_size=8,
                                      batch_size=32)
    # image_folder assigns 1-based labels by sorted class-dir name
    truth = [int(s.label) for s in samples]
    acc = float(np.mean([c == t for c, t in zip(classes, truth)]))
    print(f"predicted {len(classes)} folder images, accuracy {acc:.3f}")
    return acc


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", help="pretrained model file (BigDL format)")
    p.add_argument("--folder", help="image folder (class-per-subdir)")
    p.add_argument("--image-size", type=int, default=28)
    p.add_argument("--distributed", action="store_true")
    a = p.parse_args(argv)
    if not a.model or not a.folder:
        acc = demo()
        print("PASS" if acc > 0.8 else "FAIL")
        return
    from ..utils.file_io import load
    from ..utils.engine import Engine

    mesh = None
    if a.distributed:
        Engine.init()
        mesh = Engine.create_mesh()
    model = load(a.model)
    model.evaluate()
    classes, samples = predict_folder(model, a.folder, a.image_size,
                                      mesh=mesh)
    for s, c in list(zip(samples, classes))[:20]:
        print(f"  predicted class {c}")


if __name__ == "__main__":
    main()
