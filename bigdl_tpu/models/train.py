"""End-to-end zoo trainers (reference models/{lenet,vgg,resnet,inception,
rnn,autoencoder}/Train.scala + Options — SURVEY §1.8).

One argparse CLI replaces the per-model scopt parsers; per-model
defaults (batch size, schedule, epochs) follow the reference Train
configs.  Data comes from the hermetic loaders (real files when
``--folder`` points at MNIST/CIFAR binaries, synthetic otherwise).

Usage:
    python -m bigdl_tpu.models.train --model lenet5 --max-epoch 5
    python -m bigdl_tpu.models.train --model vgg --batch-size 128 --distributed
"""
from __future__ import annotations

import argparse
from typing import Optional

import numpy as np


def _mnist_samples(folder: Optional[str], train: bool):
    from ..dataset import Sample
    from ..dataset.datasets import (TEST_MEAN, TEST_STD, TRAIN_MEAN,
                                    TRAIN_STD, load_mnist)

    x, y = load_mnist(folder, train)
    mean, std = (TRAIN_MEAN, TRAIN_STD) if train else (TEST_MEAN, TEST_STD)
    x = (x.astype(np.float32) - mean) / std
    return [Sample(xi[None], np.float32(yi)) for xi, yi in zip(x, y)]


def _cifar_samples(folder: Optional[str], train: bool):
    from ..dataset import Sample
    from ..dataset.datasets import CIFAR_MEAN, CIFAR_STD, load_cifar10

    x, y = load_cifar10(folder, train)
    x = (x.astype(np.float32) - CIFAR_MEAN) / CIFAR_STD
    x = x.transpose(0, 3, 1, 2)  # HWC→CHW
    return [Sample(xi, np.float32(yi)) for xi, yi in zip(x, y)]


def _text_samples(vocab_size: int, seq_len: int, train: bool):
    from ..dataset import Sample
    from ..dataset.datasets import load_news20
    from ..dataset.text import Dictionary, SentenceTokenizer

    corpus = load_news20(train=train)
    tok = SentenceTokenizer()
    tokens = list(tok(iter(text for text, _ in corpus)))
    d = Dictionary(iter(tokens), vocab_size=vocab_size - 1)
    samples = []
    for toks, (_, label) in zip(tokens, corpus):
        idx = np.array([d.get_index(w) + 1 for w in toks[:seq_len]],
                       np.float32)
        if len(idx) < seq_len:
            # pad with the dedicated id (vocab_size + 1): known words map
            # to 1..vocab_size-1 and the Dictionary's OOV bucket to
            # vocab_size, so only vocab_size+1 aliases nothing;
            # LookupTable(padding_value=vocab_size+1) zeroes those rows
            idx = np.pad(idx, (0, seq_len - len(idx)),
                         constant_values=float(vocab_size + 1))
        samples.append(Sample(idx, np.float32(label)))
    return samples


def build(model_name: str, args):
    """→ (model, criterion, train_samples, val_samples, val_methods)."""
    from .. import nn
    from ..optim import Loss, Top1Accuracy

    name = model_name.lower()
    if name == "lenet5":
        from .lenet import LeNet5

        return (LeNet5(10), nn.ClassNLLCriterion(),
                _mnist_samples(args.folder, True),
                _mnist_samples(args.folder, False), [Top1Accuracy()])
    if name == "autoencoder":
        from ..dataset import Sample
        from .autoencoder import Autoencoder

        base = _mnist_samples(args.folder, True)
        flat = [Sample(np.asarray(s.feature).reshape(-1),
                       np.asarray(s.feature).reshape(-1)) for s in base]
        vflat = flat[:max(1, len(flat) // 10)]
        return (Autoencoder(32), nn.MSECriterion(), flat, vflat,
                [Loss(nn.MSECriterion())])
    if name == "vgg":
        from .vgg import VggForCifar10

        return (VggForCifar10(10), nn.ClassNLLCriterion(),
                _cifar_samples(args.folder, True),
                _cifar_samples(args.folder, False), [Top1Accuracy()])
    if name == "resnet":
        from .resnet import ResNetCifar

        return (ResNetCifar(depth=20, class_num=10),
                nn.ClassNLLCriterion(),
                _cifar_samples(args.folder, True),
                _cifar_samples(args.folder, False), [Top1Accuracy()])
    if name in ("inception_v1", "inception_v2"):
        from ..dataset import Sample
        from .inception import Inception_v1, Inception_v2

        rng = np.random.RandomState(0)
        mk = lambda n: [Sample(rng.rand(3, 224, 224).astype(np.float32),
                               np.float32(rng.randint(1, 1001)))
                        for _ in range(n)]
        model = (Inception_v1 if name == "inception_v1"
                 else Inception_v2)(1000)
        return (model, nn.ClassNLLCriterion(), mk(args.batch_size * 4),
                mk(args.batch_size), [Top1Accuracy()])
    if name == "rnn":
        from .rnn import LSTMClassifier

        V, T = 2000, 64
        # V+2 rows: ids 1..V-1 words, V = OOV bucket, V+1 = padding
        return (LSTMClassifier(V + 2, 64, 64, 20, padding_value=V + 1),
                nn.ClassNLLCriterion(),
                _text_samples(V, T, True), _text_samples(V, T, False),
                [Top1Accuracy()])
    if name == "transformer":
        from ..dataset import Sample
        from .transformer import TransformerLM

        V, T = 256, 64
        sp = getattr(args, "seq_parallel", 1) > 1
        tp = getattr(args, "tensor_parallel", 1) > 1
        # logits output: the fused CrossEntropyCriterion computes its own
        # log-sum-exp, so a log_softmax head would be pure wasted [B,T,V]
        # bandwidth at the hottest layer (models/transformer.py docstring)
        moe = getattr(args, "moe_experts", 0)
        lm = TransformerLM(
            V, embed_dim=64, num_heads=4, num_layers=2, max_len=T,
            seq_strategy="ring" if sp else "dense",
            seq_axis="seq" if sp else None,
            model_axis="model" if tp else None,
            remat=getattr(args, "remat", False),
            output="logits",
            moe_experts=moe,
            # expert parallelism rides the data axis; local training
            # keeps the dense dispatch (same function, one shard)
            moe_axis="data" if (moe and getattr(args, "distributed",
                                                False)) else None,
            moe_aux_coef=getattr(args, "moe_aux_coef", 0.0),
            moe_top_k=getattr(args, "moe_top_k", 1),
            dropout=getattr(args, "dropout", 0.0),
            # --llama: the modern decoder dialect (RMSNorm + RoPE +
            # GQA halved KV heads + SwiGLU, bias-free)
            **({"norm": "rms", "mlp": "swiglu", "rope": True,
                "num_kv_heads": 2, "head_bias": False}
               if getattr(args, "llama", False) else {}))
        crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(), True)
        # synthetic char-LM with learnable structure: next token is a
        # fixed permutation of the current one, plus noise tokens
        rng = np.random.RandomState(0)
        perm = rng.permutation(V - 1) + 1

        def mk(n, seed):
            r = np.random.RandomState(seed)
            out = []
            for _ in range(n):
                seq = np.empty(T + 1, np.int64)
                seq[0] = r.randint(1, V)
                for t in range(1, T + 1):
                    seq[t] = (perm[seq[t - 1] - 1] if r.rand() < 0.9
                              else r.randint(1, V))
                out.append(Sample(seq[:-1].astype(np.float32),
                                  (seq[1:] + 1).astype(np.float32)))
            return out

        return (lm, crit, mk(512, 1), mk(64, 2), [Loss(crit)])
    raise ValueError(f"unknown model {model_name!r}")


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="bigdl_tpu zoo trainer (reference models/*/Train.scala)")
    parser.add_argument("--model", default="lenet5",
                        choices=("lenet5", "vgg", "resnet", "inception_v1",
                                 "inception_v2", "rnn", "autoencoder",
                                 "transformer"))
    parser.add_argument("-f", "--folder", default=None,
                        help="dataset folder (synthetic data when absent)")
    parser.add_argument("-b", "--batch-size", type=int, default=None)
    parser.add_argument("-e", "--max-epoch", type=int, default=None)
    parser.add_argument("--learning-rate", type=float, default=None)
    parser.add_argument("--checkpoint", default=None)
    parser.add_argument("--summary-dir", default=None)
    parser.add_argument("--distributed", action="store_true",
                        help="DistriOptimizer over all visible devices")
    def positive_int(v):
        v = int(v)
        if v < 1:
            raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
        return v

    parser.add_argument("--tensor-parallel", type=positive_int, default=1,
                        metavar="N",
                        help="model-axis size (mesh becomes data x model; "
                             "the model must use Column/RowParallelLinear "
                             "layers to benefit; requires --distributed)")
    parser.add_argument("--seq-parallel", type=positive_int, default=1,
                        metavar="N",
                        help="seq-axis size for sequence models (ring "
                             "attention over the mesh's seq axis; "
                             "requires --distributed)")
    parser.add_argument("--pipeline-parallel", type=positive_int, default=1,
                        metavar="N",
                        help="pipe-axis size: GPipe pipeline over N "
                             "stages (transformer only; N must divide "
                             "num_layers; requires --distributed; "
                             "composes with --tensor-parallel for 3-D "
                             "data x pipe x model; excludes "
                             "--seq-parallel)")
    parser.add_argument("--pipeline-microbatch", type=positive_int,
                        default=None, metavar="M",
                        help="GPipe microbatches per step (default: the "
                             "pipe-axis size); batch size must be "
                             "divisible by data-shards x M")
    def nonneg_int(v):
        v = int(v)
        if v < 0:
            raise argparse.ArgumentTypeError(f"must be >= 0, got {v}")
        return v

    parser.add_argument("--moe-experts", type=nonneg_int, default=0,
                        metavar="E",
                        help="swap the transformer MLP for a Switch-style "
                             "mixture of E experts (transformer only); "
                             "with --distributed the experts shard over "
                             "the data axis (expert parallelism, "
                             "all_to_all dispatch) and E must be "
                             "divisible by the data-shard count")
    parser.add_argument("--llama", action="store_true",
                        help="llama-style transformer blocks: RMSNorm + "
                             "rotary positions + grouped-query attention "
                             "(2 KV heads) + SwiGLU, bias-free "
                             "(transformer only; not with --seq-parallel "
                             "— rope needs global positions)")
    parser.add_argument("--moe-top-k", type=int, default=1, metavar="K",
                        help="experts per token: 1 = Switch (raw gate), "
                             "2 = GShard-style (renormalized gates, "
                             "first choices claim capacity first)")
    parser.add_argument("--moe-aux-coef", type=float, default=0.0,
                        metavar="C",
                        help="Switch load-balance auxiliary loss "
                             "coefficient (0 disables; 0.01 is the "
                             "Switch Transformer default)")
    parser.add_argument("--dropout", type=float, default=0.0,
                        help="residual dropout in the transformer blocks "
                             "(train-time only; per-shard decorrelated "
                             "keys on distributed meshes)")
    parser.add_argument("--remat", action="store_true",
                        help="rematerialize transformer-block activations "
                             "in the backward pass (jax.checkpoint): HBM "
                             "for FLOPs on long contexts; transformer only")
    parser.add_argument("--conv-impl", default=None,
                        choices=("xla", "xla_nhwc", "gemm", "pallas"),
                        help="conv lowering for spatial models: XLA's "
                             "native conv (NCHW), the same conv with "
                             "activations flowing NHWC between boundary "
                             "transposes (xla_nhwc — the layout "
                             "experiment), the k²-matmul decomposition "
                             "(ops/conv_gemm — MXU-shaped matmuls, no "
                             "im2col materialization), or the Pallas "
                             "slab kernel for 3×3/s1 shapes")
    args = parser.parse_args(argv)
    if args.conv_impl:
        import os

        os.environ["bigdl.conv.impl"] = args.conv_impl
    if ((args.tensor_parallel > 1 or args.seq_parallel > 1
         or args.pipeline_parallel > 1) and not args.distributed):
        parser.error("--tensor-parallel/--seq-parallel/--pipeline-parallel "
                     "require --distributed")
    if args.pipeline_parallel > 1 and args.seq_parallel > 1:
        parser.error("--pipeline-parallel composes with data/tensor "
                     "parallelism, not --seq-parallel")
    if args.pipeline_parallel > 1 and args.model != "transformer":
        parser.error("--pipeline-parallel supports --model transformer")
    if args.pipeline_microbatch and args.pipeline_parallel < 2:
        parser.error("--pipeline-microbatch needs --pipeline-parallel >= 2 "
                     "(it configures the GPipe schedule)")
    if getattr(args, "llama", False):
        if args.model != "transformer":
            parser.error("--llama supports --model transformer")
        if args.seq_parallel > 1:
            parser.error("--llama (rope) needs global positions; it "
                         "does not compose with --seq-parallel")
        if args.moe_experts:
            parser.error("--llama (swiglu) does not compose with "
                         "--moe-experts (gelu expert MLPs)")
    if args.moe_experts and args.model != "transformer":
        parser.error("--moe-experts supports --model transformer")
    if args.moe_experts and (args.tensor_parallel > 1
                             or args.pipeline_parallel > 1):
        parser.error("--moe-experts composes with data and sequence "
                     "parallelism (expert parallelism rides the data "
                     "axis), not --tensor-parallel/--pipeline-parallel")

    from ..utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()

    # per-model defaults from the reference Train configs
    defaults = {
        "lenet5": (128, 5, 0.05),        # models/lenet/Train.scala
        "vgg": (128, 10, 0.01),          # models/vgg/Train.scala
        "resnet": (128, 10, 0.1),        # models/resnet/Train.scala batch 448
        "inception_v1": (32, 1, 0.01),
        "inception_v2": (32, 1, 0.01),
        "rnn": (32, 5, 0.1),             # models/rnn/Train.scala
        "autoencoder": (128, 5, 0.01),
        "transformer": (32, 2, 0.1),     # long-context extension workload
    }[args.model]
    batch = args.batch_size or defaults[0]
    epochs = args.max_epoch or defaults[1]
    # `is None` not `or`: an explicit --learning-rate 0 is a legitimate
    # frozen-weights request, not a request for the default
    lr = defaults[2] if args.learning_rate is None else args.learning_rate

    from .. import nn  # noqa: F401 — force registry
    from ..dataset.dataset import array
    from ..optim import SGD, Top1Accuracy, every_epoch, max_epoch
    from ..optim.optimizer import LocalOptimizer
    from ..utils.engine import Engine

    Engine.init()
    model, criterion, train_s, val_s, v_methods = build(args.model, args)

    if args.distributed:
        from ..optim.distri_optimizer import DistriOptimizer

        # Engine.create_mesh validates divisibility; model/seq > 1 route
        # DistriOptimizer onto the multi-axis SPMD path, pipe > 1 onto
        # the GPipe pipeline path
        mesh = Engine.create_mesh(model=args.tensor_parallel,
                                  seq=args.seq_parallel,
                                  pipe=args.pipeline_parallel)
        opt = DistriOptimizer(model, array(train_s), criterion,
                              batch_size=batch, mesh=mesh)
        if args.pipeline_microbatch:
            opt.set_pipeline_microbatch(args.pipeline_microbatch)
    else:
        opt = LocalOptimizer(model, array(train_s), criterion,
                             batch_size=batch)
    opt.set_optim_method(SGD(learning_rate=lr))
    opt.set_end_when(max_epoch(epochs))
    opt.set_validation(every_epoch(), array(val_s), v_methods,
                       batch_size=batch)
    if args.checkpoint:
        opt.set_checkpoint(args.checkpoint, every_epoch())
    if args.summary_dir:
        from ..visualization.summary import TrainSummary

        opt.set_train_summary(TrainSummary(args.summary_dir, args.model))
    opt.optimize()
    return model


if __name__ == "__main__":
    main()
