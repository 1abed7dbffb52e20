"""Real-data convergence with crash-resume: fine-tune a torch-initialized
mid-size GPT-2 on a REAL text corpus through the multi-axis driver.

The reference documents its zoo's convergence on real datasets
(models/resnet/README.md:30-68: ResNet-20/CIFAR-10 to accuracy over 156
epochs); this offline image ships no CIFAR/PTB blobs, so the corpus is
the real English text the image DOES carry: this repo's own markdown
docs plus the markdown shipped inside site-packages (README/guides of
the installed libraries) — ~100k words of genuine prose, word-level
tokenized through the framework's own text pipeline
(SentenceTokenizer → Dictionary, reference dataset/text/ parity).

The model is a ~6M-parameter GPT-2 authored BY torch (transformers,
seeded), imported via ``interop.load_gpt2``, and re-hosted into a
ring-attention + Megatron-split TransformerLM (the param tree is
config-independent) so training runs through the FULL dp×sp×tp
multi-axis DistriOptimizer on a 2x2x2 mesh with async sharded Orbax
checkpoints.  Perplexity on a held-out split is appended to a JSONL
trajectory at every segment end; the outer harness
(tools/convergence_run.sh) kill -9s the process mid-run and restarts
it, and the resumed segment must continue from the last committed
Orbax step (``resumed_from`` in the trajectory records it).

Runs on the 8-virtual-device CPU mesh: launch with ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the harness does).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time

import numpy as np

T = 32            # training sequence length (positions table is 64)
VOCAB = 8000      # GPT-2 vocab (OOV bucket = id 8000)
BATCH = 8
GPT2_KW = dict(vocab_size=VOCAB, n_positions=64, n_embd=256, n_layer=4,
               n_head=8, attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0)


def _corpus_texts():
    """Real markdown prose available in-image: the repo's docs and the
    installed packages' own markdown."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    paths = sorted(glob.glob(os.path.join(repo, "*.md"))) + \
        sorted(glob.glob(os.path.join(repo, "docs", "*.md")))
    import sysconfig

    site = sorted(glob.glob(os.path.join(
        sysconfig.get_paths()["purelib"], "**", "*.md"), recursive=True))
    for p in paths + site[:400]:
        try:
            with open(p, errors="ignore") as f:
                yield f.read()
        except OSError:
            continue


def build_corpus(cache="/tmp/convergence_corpus.npz"):
    """Tokenize through the text pipeline; returns (train_ids, val_ids)
    as flat 1-based int32 arrays (cached — the corpus is static)."""
    if os.path.exists(cache):
        z = np.load(cache)
        return z["train"], z["val"]
    from ..dataset.text import Dictionary, SentenceTokenizer

    tok = SentenceTokenizer()
    sentences = list(tok.apply(iter(_corpus_texts())))
    d = Dictionary(sentences, vocab_size=VOCAB - 1)
    flat = np.fromiter(
        (d.get_index(w) + 1 for s in sentences for w in s), np.int32)
    # deterministic 90/10 split at document granularity is overkill for
    # a trajectory proof; contiguous split keeps val text truly unseen
    n_val = len(flat) // 10
    print(f"corpus: {len(flat)} tokens, {d.vocab_size()} vocab words, "
          f"{n_val} held out")
    np.savez(cache, train=flat[:-n_val], val=flat[-n_val:])
    return flat[:-n_val], flat[-n_val:]


def _windows(flat, seed=None):
    """[N, T+1] next-token windows (x=w[:,:-1], y=w[:,1:])."""
    n = (len(flat) - 1) // T
    w = np.stack([flat[i * T:i * T + T + 1] for i in range(n)])
    if seed is not None:
        np.random.RandomState(seed).shuffle(w)
    return w


def _minibatches(windows):
    from ..dataset.sample import MiniBatch

    out = []
    for i in range(0, len(windows) - BATCH + 1, BATCH):
        w = windows[i:i + BATCH]
        out.append(MiniBatch(w[:, :-1].astype(np.float32),
                             w[:, 1:].astype(np.float32)))
    return out


def build_model(llama: bool = False):
    """Torch-authored init checkpoint (deterministic, cached) →
    interop loader → re-hosted into the multi-axis TransformerLM.

    Default: GPT-2 dialect, trained dp×sp×tp (ring attention over
    'seq' + Megatron split over 'model').  ``llama=True``: the Llama
    dialect (RMSNorm + RoPE + GQA + SwiGLU) — rope needs global
    positions, so it trains dp×tp (no seq axis)."""
    import torch
    import transformers

    from ..interop.huggingface import load_gpt2, load_llama
    from ..models.transformer import TransformerLM

    if llama:
        ckpt = "/tmp/convergence_llama_init.pt"
        cfg = transformers.LlamaConfig(
            vocab_size=VOCAB, hidden_size=256, intermediate_size=688,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=2, max_position_embeddings=64,
            attention_bias=False, tie_word_embeddings=False)
        torch.manual_seed(4242)
        hf = transformers.LlamaForCausalLM(cfg)
    else:
        ckpt = "/tmp/convergence_gpt2_init.pt"
        torch.manual_seed(4242)
        hf = transformers.GPT2LMHeadModel(
            transformers.GPT2Config(**GPT2_KW))
    if os.path.exists(ckpt):
        hf.load_state_dict(torch.load(ckpt, weights_only=True))
    else:
        torch.save(hf.state_dict(), ckpt)
    # GPT-2 ties lm_head to the embedding (don't double-count); the
    # llama config is untied, so its head is a real trained matrix
    n_params = sum(p.numel() for n, p in hf.named_parameters()
                   if llama or n != "lm_head.weight")
    if llama:
        lm0 = load_llama(hf.eval())
        lm = TransformerLM(VOCAB, embed_dim=256, num_heads=8,
                           mlp_dim=688, num_layers=4, max_len=64,
                           norm="rms", mlp="swiglu", num_kv_heads=2,
                           rope=True, attn_bias=False, head_bias=False,
                           model_axis="model")
    else:
        lm0 = load_gpt2(hf.eval())
        lm = TransformerLM(VOCAB, embed_dim=GPT2_KW["n_embd"],
                           num_heads=GPT2_KW["n_head"],
                           mlp_dim=4 * GPT2_KW["n_embd"],
                           num_layers=GPT2_KW["n_layer"],
                           max_len=GPT2_KW["n_positions"],
                           seq_strategy="ring", model_axis="model")
    lm.set_param_tree(lm0.param_tree())
    print(f"model: {n_params / 1e6:.2f}M params (torch-initialized"
          f"{', llama dialect' if llama else ''})")
    return lm


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=40,
                    help="iterations to add in this segment")
    ap.add_argument("--ckpt-dir", default=None,
                    help="default: /tmp/convergence_ckpt "
                         "(or _llama_ckpt with --llama)")
    ap.add_argument("--log", default=None,
                    help="default: LONGRUN_CONVERGENCE.jsonl "
                         "(or _LLAMA with --llama)")
    ap.add_argument("--llama", action="store_true",
                    help="llama dialect (RMSNorm+RoPE+GQA+SwiGLU), "
                         "trained dp x tp instead of dp x sp x tp")
    args = ap.parse_args(argv)
    # dialect-specific defaults: resuming a GPT-2 orbax tree into a
    # llama model (different param structure) must be impossible by
    # default, and the two trajectories must not interleave in one file
    if args.ckpt_dir is None:
        args.ckpt_dir = ("/tmp/convergence_llama_ckpt" if args.llama
                         else "/tmp/convergence_ckpt")
    if args.log is None:
        args.log = ("LONGRUN_CONVERGENCE_LLAMA.jsonl" if args.llama
                    else "LONGRUN_CONVERGENCE.jsonl")
    # explicit dirs still refuse a dialect mismatch
    marker = os.path.join(args.ckpt_dir, "dialect.txt")
    dialect = "llama" if args.llama else "gpt2"
    if os.path.exists(marker):
        prev = open(marker).read().strip()
        if prev != dialect:
            raise SystemExit(
                f"checkpoint dir {args.ckpt_dir} holds a {prev!r} "
                f"run; refusing to resume it as {dialect!r} — the "
                "param trees are structurally different")
    elif os.path.isdir(args.ckpt_dir) and os.listdir(args.ckpt_dir):
        raise SystemExit(
            f"checkpoint dir {args.ckpt_dir} is non-empty but carries "
            "no dialect marker (pre-marker run?) — refusing to guess; "
            "point --ckpt-dir elsewhere or remove the old tree")
    else:
        os.makedirs(args.ckpt_dir, exist_ok=True)
        with open(marker, "w") as f:
            f.write(dialect)

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from .. import nn
    from ..dataset.dataset import array
    from ..optim import Adam, Trigger, several_iteration
    from ..optim.distri_optimizer import DistriOptimizer
    from ..optim.evaluator import evaluate_dataset
    from ..optim.validation import Loss
    from ..parallel.spmd import make_eval_forward
    from ..utils.engine import Engine

    Engine.init()
    train_flat, val_flat = build_corpus()
    train_mb = _minibatches(_windows(train_flat, seed=11))
    val_mb = _minibatches(_windows(val_flat))
    if args.llama:  # rope needs global positions: no seq axis
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                    ("data", "model"))
    else:
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                    ("data", "seq", "model"))

    model = build_model(llama=args.llama)
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(), True)
    opt = DistriOptimizer(model, array(train_mb), crit,
                          batch_size=BATCH, mesh=mesh)
    opt.set_optim_method(Adam(learning_rate=3e-4))
    opt.set_checkpoint(args.ckpt_dir, several_iteration(10),
                       format="orbax")
    opt.overwrite_checkpoint()

    resumed_from = None
    if os.path.isdir(args.ckpt_dir) and opt.resume_from_checkpoint():
        resumed_from = opt.optim_method.state["neval"] - 1
        print(f"resumed from orbax step {resumed_from}")

    start_iter = opt.optim_method.state.get("neval", 1) - 1
    until = start_iter + args.iters

    opt.set_end_when(Trigger(
        lambda state: state.get("neval", 1) - 1 >= until,
        f"until{until}"))
    t0 = time.time()
    opt.optimize()
    train_secs = time.time() - t0

    # held-out perplexity through the on-mesh eval forward (ring
    # attention cannot run eagerly)
    fwd = make_eval_forward(model, mesh)
    res = evaluate_dataset(model, array(val_mb), [Loss(crit)],
                           batch_size=BATCH, fwd=fwd,
                           n_shard=4 if args.llama else 2)
    val_loss = res[0].result()[0]
    row = {
        "iteration": opt.optim_method.state["neval"] - 1,
        "train_loss": round(float(opt.optim_method.state["loss"]), 4),
        "val_loss": round(float(val_loss), 4),
        "val_ppl": round(float(np.exp(val_loss)), 2),
        "segment_secs": round(train_secs, 1),
        "resumed_from": resumed_from,
        "time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    with open(args.log, "a") as f:
        f.write(json.dumps(row) + "\n")
    print("segment:", json.dumps(row))


if __name__ == "__main__":
    main()
