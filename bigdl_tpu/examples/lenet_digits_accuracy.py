"""Train-to-accuracy proof: LeNet-5 on REAL handwritten digits through
the full Optimizer lifecycle (reference models/lenet/Train.scala;
accuracy bar from models/resnet/README.md-style zoo targets).

This offline image ships no MNIST idx blobs (the reference's own
src/test/resources/mnist fixture is stripped to labels only), so the
real-data proof uses scikit-learn's bundled `load_digits` — 1797 genuine
8x8 handwritten digit scans (UCI Optical Recognition of Handwritten
Digits) — upscaled to LeNet-5's 28x28 input.  When a MNIST folder IS
available, ``bigdl_tpu.models.train --model lenet5 -f <dir>`` runs the
identical lifecycle on it.

Exercised end-to-end: LocalOptimizer + SGD(momentum) + Trigger DSL +
Top1Accuracy validation + TrainSummary/ValidationSummary event files +
checkpointing + restore-from-checkpoint evaluation.

Run:  JAX_PLATFORMS=cpu python -m bigdl_tpu.examples.lenet_digits_accuracy
"""
from __future__ import annotations

import os
import sys
import tempfile

import numpy as np


def digits_as_mnist():
    """(train_samples, test_samples): 8x8 digits upscaled to 28x28,
    flattened to LeNet-5's (784,) input contract, 1-based labels."""
    from sklearn.datasets import load_digits

    from bigdl_tpu.dataset import Sample

    d = load_digits()
    imgs = d.images.astype(np.float32) / 16.0          # (N, 8, 8) in [0,1]
    up = np.repeat(np.repeat(imgs, 3, axis=1), 3, axis=2)  # (N, 24, 24)
    up = np.pad(up, ((0, 0), (2, 2), (2, 2)))          # (N, 28, 28)
    flat = up.reshape(len(up), -1)
    labels = d.target.astype(np.float32) + 1           # 1-based
    rng = np.random.RandomState(0)
    order = rng.permutation(len(flat))
    flat, labels = flat[order], labels[order]
    n_train = 1500
    mk = lambda lo, hi: [Sample(flat[i], labels[i]) for i in range(lo, hi)]
    return mk(0, n_train), mk(n_train, len(flat))


def main(max_epoch_n: int = 60, target: float = 0.98) -> float:
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import array
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.optim import (SGD, LocalOptimizer, Loss, Top1Accuracy,
                                 every_epoch, max_epoch)
    from bigdl_tpu.utils.rng import set_global_seed
    from bigdl_tpu.visualization import TrainSummary, ValidationSummary

    set_global_seed(1)
    train, test = digits_as_mnist()
    workdir = tempfile.mkdtemp(prefix="lenet_digits_")
    ckpt = os.path.join(workdir, "ckpt")
    logdir = os.path.join(workdir, "logs")

    model = LeNet5(10)
    opt = LocalOptimizer(model, array(train), nn.ClassNLLCriterion(),
                         batch_size=100)
    opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9,
                             learning_rate_decay=1e-4))
    opt.set_end_when(max_epoch(max_epoch_n))
    opt.set_validation(every_epoch(), array(test),
                       [Top1Accuracy(), Loss()], batch_size=100)
    opt.set_checkpoint(ckpt, every_epoch())
    opt.set_train_summary(TrainSummary(logdir, "lenet-digits"))
    opt.set_validation_summary(ValidationSummary(logdir, "lenet-digits"))
    trained = opt.optimize()

    res = trained.evaluate(array(test), [Top1Accuracy()])
    acc = res[0][0].result()[0]
    print(f"\nFinal Top1Accuracy on held-out digits: {acc:.4f} "
          f"(target {target:.2f}) over {len(test)} samples")

    # restore the numerically-latest checkpoint and re-evaluate: the
    # persisted artifact must reproduce the accuracy
    from bigdl_tpu.optim.distri_optimizer import _latest_file
    from bigdl_tpu.utils.file_io import load_module

    latest = _latest_file(ckpt, "model")
    restored = load_module(latest)
    res2 = restored.evaluate(array(test), [Top1Accuracy()])
    acc2 = res2[0][0].result()[0]
    print(f"Restored checkpoint {os.path.basename(latest)} Top1Accuracy: "
          f"{acc2:.4f}")
    assert abs(acc - acc2) < 1e-6, "checkpoint must reproduce the model"
    return acc


if __name__ == "__main__":
    accuracy = main()
    ok = accuracy >= 0.98
    print("PASS" if ok else "FAIL", f"accuracy={accuracy:.4f}")
    sys.exit(0 if ok else 1)
