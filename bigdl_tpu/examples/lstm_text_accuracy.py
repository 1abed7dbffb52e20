"""Train-to-accuracy proof for the RECURRENT stack: the LSTM text
classifier (BASELINE.md workload 5, reference models/rnn + the LSTM/GRU
text-classification config) trained through the full Optimizer lifecycle
to a stated Top1 target.

The task requires genuine memory because of the model's own head, not
the data: the class marker sits at a random position in the FIRST
QUARTER of the sequence with 15+ uniform distractor tokens after it,
and the classifier reads ONLY the last timestep's hidden state
(``Select(2, -1)``) — the marker signal must survive 15+ scan steps
inside the LSTM state to reach the head.  (A head pooling over all
timesteps could solve this bag-of-words-style; this one cannot.)

Run:  JAX_PLATFORMS=cpu python -m bigdl_tpu.examples.lstm_text_accuracy
(set BIGDL_EXAMPLES_PLATFORM=device to run on the preloaded accelerator)
"""
from __future__ import annotations

import os
import sys
import tempfile

import numpy as np


VOCAB = 40
T = 20
CLASSES = 4
MARKERS = list(range(1, 1 + CLASSES))  # token ids 1..4 are class markers


def make_dataset(n: int, seed: int):
    """Sequences of distractor tokens (ids 5..VOCAB-1) with one class
    marker hidden in the first quarter; labels 1-based."""
    from bigdl_tpu.dataset import Sample

    rng = np.random.RandomState(seed)
    samples = []
    for _ in range(n):
        cls = int(rng.randint(CLASSES))
        seq = rng.randint(1 + CLASSES, VOCAB, size=T)
        seq[rng.randint(T // 4)] = MARKERS[cls]
        # LookupTable ids are 1-based; distractors already >= 5
        samples.append(Sample(seq.astype(np.float32),
                              np.float32(cls + 1)))
    return samples


def main(max_epoch_n: int = 25, target: float = 0.95,
         cell: str = "lstm") -> float:
    from bigdl_tpu import nn
    from bigdl_tpu.dataset import array
    from bigdl_tpu.models.rnn import LSTMClassifier
    from bigdl_tpu.optim import (Adam, LocalOptimizer, Top1Accuracy,
                                 every_epoch, max_epoch)
    from bigdl_tpu.utils.rng import set_global_seed

    set_global_seed(7)
    train, test = make_dataset(2000, seed=1), make_dataset(400, seed=2)

    model = LSTMClassifier(VOCAB, embed_dim=16, hidden=32,
                           class_num=CLASSES, cell=cell)
    ckpt = tempfile.mkdtemp(prefix="lstm_text_")
    opt = LocalOptimizer(model, array(train), nn.ClassNLLCriterion(),
                         batch_size=100)
    opt.set_optim_method(Adam(learning_rate=0.01))
    opt.set_end_when(max_epoch(max_epoch_n))
    opt.set_validation(every_epoch(), array(test), [Top1Accuracy()],
                       batch_size=100)
    opt.set_checkpoint(ckpt, every_epoch())
    trained = opt.optimize()

    from bigdl_tpu.optim.evaluator import LocalValidator

    result = LocalValidator(trained).test(array(test), [Top1Accuracy()],
                                          batch_size=100)
    acc = result[0][0].result()[0]
    print(f"Final {cell.upper()} Top1Accuracy on held-out sequences: {acc:.4f} "
          f"(target {target}) over 400 samples")

    # restore-from-checkpoint exactness (same contract as the other proofs)
    from bigdl_tpu import api
    from bigdl_tpu.optim.distri_optimizer import _latest_file

    latest = _latest_file(ckpt, "model")
    restored = api.load_bigdl(latest)
    r_acc = LocalValidator(restored).test(array(test), [Top1Accuracy()],
                                          batch_size=100)[0][0].result()[0]
    print(f"Restored checkpoint {os.path.basename(latest)} "
          f"Top1Accuracy: {r_acc:.4f}")
    assert abs(r_acc - acc) < 1e-6, (
        f"restored checkpoint accuracy {r_acc} != live {acc}")
    status = "PASS" if acc >= target else "FAIL"
    print(f"{status} accuracy={acc:.4f}")
    return acc


if __name__ == "__main__":
    sys.exit(0 if main() >= 0.95 else 1)
