"""DISTRIBUTED train-to-accuracy proof: ResNet-CIFAR topology through
DistriOptimizer on an 8-device mesh (VERDICT r2 #8; reference
models/resnet/README.md:30-68 trains ResNet-20/CIFAR-10 distributed,
DistriOptimizerSpec.scala:32-60 proves the driver trains to target).

Data caveat (same as docs/ACCURACY.md): this offline image ships no
CIFAR blobs, so the real-data proof uses scikit-learn's bundled
``load_digits`` — 1797 genuine handwritten 8x8 scans — upscaled to the
model's 3x32x32 CIFAR input contract.  When a CIFAR-10 folder IS
available, ``bigdl_tpu.models.train --model resnet -f <dir>`` runs the
identical lifecycle on it.

Exercised end-to-end, all on the mesh: the shard_mapped train step
(all_gather -> fwd/bwd -> psum_scatter -> slice-owned SGD+momentum
update), sharded optimizer slots, pad-and-mask trailing partial batches
(1500 % 64 = 28 records, 28 % 8 != 0 -> masked step), on-mesh validation
triggers, per-epoch checkpoints, and a restore-from-checkpoint
re-evaluation that must reproduce the final accuracy exactly.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m bigdl_tpu.examples.resnet_digits_distributed_accuracy
"""
from __future__ import annotations

import os
import sys
import tempfile

import numpy as np


def digits_as_cifar():
    """(train_samples, test_samples): 8x8 digit scans upscaled to the
    ResNet-CIFAR (3, 32, 32) input contract, 1-based labels."""
    return digits_upscaled(4)


def digits_upscaled(factor: int, n_train: int = 1500):
    """Shared data pipeline for the train-to-accuracy proofs: the 1797
    real 8x8 digit scans, nearest-upscaled by ``factor``, replicated to
    3 channels (CHW), normalized, seed-0 shuffled, split
    ``n_train``/rest.  Labels 1-based."""
    from sklearn.datasets import load_digits

    from bigdl_tpu.dataset import Sample

    d = load_digits()
    imgs = d.images.astype(np.float32) / 16.0              # (N, 8, 8)
    up = np.repeat(np.repeat(imgs, factor, axis=1), factor, axis=2)
    chw = np.repeat(up[:, None, :, :], 3, axis=1)          # (N, 3, s, s)
    chw = (chw - chw.mean()) / (chw.std() + 1e-7)
    labels = d.target.astype(np.float32) + 1               # 1-based
    rng = np.random.RandomState(0)
    order = rng.permutation(len(chw))
    chw, labels = chw[order], labels[order]
    mk = lambda lo, hi: [Sample(chw[i], labels[i]) for i in range(lo, hi)]
    return mk(0, n_train), mk(n_train, len(chw))


def main(max_epoch_n: int = 30, depth: int = 20, target: float = 0.97,
         batch_size: int = 64) -> float:
    from bigdl_tpu.models.resnet import ResNetCifar

    from ._distributed_proof import run_distributed_proof

    # reference ResNet training recipe: SGD + momentum + weight decay
    return run_distributed_proof(
        lambda: ResNetCifar(depth=depth, class_num=10,
                            shortcut_type="A"), seed=1,
        sgd_kwargs=dict(learning_rate=0.1, momentum=0.9, weight_decay=1e-4,
                        nesterov=True, dampening=0.0),
        max_epoch_n=max_epoch_n, target=target, batch_size=batch_size,
        ckpt_prefix="bigdl_resnet_ckpt_", label="ResNet")


if __name__ == "__main__":
    acc = main()
    sys.exit(0 if acc >= 0.97 else 1)
