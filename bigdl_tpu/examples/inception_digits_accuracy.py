"""Train-to-accuracy proof for Inception v1 — the last zoo family with
throughput numbers but no accuracy run (VERDICT r3 weak #5).

Same lifecycle and data caveat as the ResNet/VGG proofs
(docs/ACCURACY.md): this offline image ships no ImageNet blobs, so the
real-data run uses scikit-learn's bundled ``load_digits`` — 1797
genuine handwritten 8x8 scans — upscaled to Inception's 3x224x224 input
contract (the canonical topology needs >=193 px for its 7x7 global
average pool; reference Inception_v1.scala trains at 224).  When an
ImageNet folder IS available, ``bigdl_tpu.models.train --model
inception-v1 -f <dir>`` runs the identical lifecycle on it.

224 px x Inception v1 is too heavy for the CPU-mesh variant of the
other proofs, so this one is sized for a real accelerator: run it with
``BIGDL_EXAMPLES_PLATFORM=device`` on the TPU (single-chip mesh — the
DistriOptimizer lifecycle, masked trailing batches, on-mesh validation
and checkpoint/restore paths are identical to the 8-device runs, which
``tests/test_distri_multi_axis.py`` covers on the virtual mesh).

Run:  BIGDL_EXAMPLES_PLATFORM=device \
        python -m bigdl_tpu.examples.inception_digits_accuracy
"""
from __future__ import annotations

import sys


def digits_as_imagenet224():
    """(train_samples, test_samples): 8x8 digit scans upscaled to the
    Inception (3, 224, 224) input contract, 1-based labels.  The
    materialized set is 1797 * 3 * 224^2 f32 = 1.1 GB — fits any host."""
    from .resnet_digits_distributed_accuracy import digits_upscaled

    return digits_upscaled(28)


def main(max_epoch_n: int = 12, target: float = 0.95,
         batch_size: int = 64) -> float:
    # 1500 % 64 = 28: every epoch ends in a masked partial batch, same
    # every-record guarantee the ResNet proof exercises
    from bigdl_tpu.models.inception import InceptionV1NoAuxClassifier

    from ._distributed_proof import run_distributed_proof

    # reference googlenet recipe shape (SGD + momentum + weight decay),
    # lr scaled for the tiny 10-class substitute task
    return run_distributed_proof(
        lambda: InceptionV1NoAuxClassifier(class_num=10), seed=1,
        sgd_kwargs=dict(learning_rate=0.03, momentum=0.9,
                        weight_decay=1e-4, nesterov=True, dampening=0.0),
        max_epoch_n=max_epoch_n, target=target, batch_size=batch_size,
        ckpt_prefix="bigdl_inception_ckpt_", label="Inception-v1",
        data_fn=digits_as_imagenet224)


if __name__ == "__main__":
    acc = main()
    sys.exit(0 if acc >= 0.95 else 1)
