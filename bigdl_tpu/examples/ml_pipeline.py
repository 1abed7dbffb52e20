"""ML-pipeline examples (reference example/MLPipeline/
DLClassifierLeNet.scala, DLClassifierLogisticRegression.scala,
DLEstimatorMultiLabelLR.scala): the estimator/transformer API over
plain (features, labels) arrays — the reference's Spark DataFrame
becomes the host array batch, everything else keeps its shape.

Usage: JAX_PLATFORMS=cpu python -m bigdl_tpu.examples.ml_pipeline
"""
from __future__ import annotations

import numpy as np


def classifier_lenet(n=512, epochs=8):
    """DLClassifierLeNet.scala: LeNet-5 through DLClassifier."""
    from .. import nn
    from ..ml import DLClassifier
    from ..models.lenet import LeNet5
    from ..optim import SGD

    from .lenet_digits_accuracy import digits_as_mnist

    train, test = digits_as_mnist()
    feats = np.stack([np.asarray(s.feature) for s in train[:n]])
    labels = np.asarray([float(s.label) for s in train[:n]])

    est = (DLClassifier(LeNet5(10), nn.ClassNLLCriterion(), [784])
           .set_batch_size(64).set_max_epoch(epochs)
           .set_optim_method(SGD(learning_rate=0.1)))
    dl_model = est.fit(feats, labels)

    tfeats = np.stack([np.asarray(s.feature) for s in test])
    tlabels = np.asarray([float(s.label) for s in test])
    pred = dl_model.transform(tfeats)
    acc = float((pred == tlabels).mean())
    print(f"DLClassifier LeNet accuracy: {acc:.4f}")
    return acc


def logistic_regression(n=256, epochs=40):
    """DLClassifierLogisticRegression.scala: Linear+LogSoftMax binary."""
    from .. import nn
    from ..ml import DLClassifier
    from ..optim import SGD

    rng = np.random.RandomState(0)
    x = rng.randn(n, 2).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.float32) + 1  # 1-based classes

    model = nn.Sequential(nn.Linear(2, 2), nn.LogSoftMax())
    est = (DLClassifier(model, nn.ClassNLLCriterion(), [2])
           .set_batch_size(32).set_max_epoch(epochs)
           .set_optim_method(SGD(learning_rate=0.5)))
    pred = est.fit(x, y).transform(x)
    acc = float((pred == y).mean())
    print(f"DLClassifier logistic-regression accuracy: {acc:.4f}")
    return acc


def multi_label_lr(n=256, epochs=60):
    """DLEstimatorMultiLabelLR.scala: 2-dim label regression through
    DLEstimator (label size (2,), MSE)."""
    from .. import nn
    from ..ml import DLEstimator
    from ..optim import SGD

    rng = np.random.RandomState(1)
    x = rng.randn(n, 2).astype(np.float32)
    w = np.array([[2.0, -1.0], [0.5, 1.5]], np.float32)
    y = x @ w

    est = (DLEstimator(nn.Linear(2, 2), nn.MSECriterion(), [2], [2])
           .set_batch_size(32).set_max_epoch(epochs)
           .set_optim_method(SGD(learning_rate=0.1)))
    pred = est.fit(x, y).transform(x)
    mse = float(((pred.reshape(n, 2) - y) ** 2).mean())
    print(f"DLEstimator multi-label LR mse: {mse:.5f}")
    return mse


def main():
    acc1 = classifier_lenet()
    acc2 = logistic_regression()
    mse = multi_label_lr()
    ok = acc1 > 0.8 and acc2 > 0.9 and mse < 0.05
    print("PASS" if ok else "FAIL")
    return ok


if __name__ == "__main__":
    import sys

    sys.exit(0 if main() else 1)
