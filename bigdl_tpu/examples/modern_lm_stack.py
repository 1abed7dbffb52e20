"""Modern-LM stack walkthrough: the modern-LM surface in one
end-to-end journey.

1. build a (tiny) GPT-2 — or, with ``--llama``, a Llama
   (RMSNorm+RoPE+GQA+SwiGLU) — in torch ``transformers`` and LOAD its
   weights into :class:`TransformerLM` (interop/huggingface.py);
2. fine-tune it with the full DistriOptimizer lifecycle on an 8-device
   mesh — optionally GPipe-pipelined (``--pipeline 2``) or Switch-MoE
   from scratch (``--moe 8``, divisible by the shard count) — with
   optax AdamW and ASYNC orbax sharded checkpoints;
3. resume from the newest checkpoint like a crashed run would;
4. GENERATE from the fine-tuned model (KV-cache decode, greedy and
   nucleus sampling) and EXPORT the result back to torch
   (``save_gpt2`` / ``save_llama``), verifying torch's decode matches.

Everything runs hermetically on the 8-virtual-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) or on real
chips with ``BIGDL_EXAMPLES_PLATFORM=device``.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python -m bigdl_tpu.examples.modern_lm_stack [--moe 8|--pipeline 2]
"""
from __future__ import annotations

import argparse
import tempfile


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--moe", type=int, default=0,
                        help="train a Switch-MoE LM from scratch with "
                             "E experts instead of the GPT-2 load")
    parser.add_argument("--pipeline", type=int, default=0,
                        help="GPipe stages (mesh data x pipe)")
    parser.add_argument("--llama", action="store_true",
                        help="start from a torch Llama checkpoint "
                             "(RMSNorm+RoPE+GQA+SwiGLU) instead of "
                             "GPT-2; exports back via save_llama")
    parser.add_argument("--iterations", type=int, default=60)
    args = parser.parse_args(argv)
    if args.moe and args.pipeline:
        parser.error("--moe and --pipeline are separate demos")
    if args.llama and (args.moe or args.pipeline):
        parser.error("--llama is the interop demo; run it alone")
    if args.iterations < 20:
        parser.error("--iterations must be >= 20 (the first fit must "
                     "reach the iteration-10 checkpoint the resume step "
                     "restores from)")

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from .. import nn
    from ..dataset.dataset import array
    from ..dataset.sample import Sample
    from ..models.transformer import TransformerLM
    from ..optim import OptaxMethod, max_iteration, several_iteration
    from ..optim.distri_optimizer import DistriOptimizer
    from ..utils.rng import RNG

    V, T = 32, 16

    # -- 1. the model: GPT-2-loaded, or MoE/pipelined from scratch -----
    def build_scratch():
        RNG().set_seed(0)
        return TransformerLM(V, embed_dim=32, num_heads=4, mlp_dim=64,
                             num_layers=max(args.pipeline, 2) * 2,
                             max_len=2 * T,
                             moe_experts=args.moe,
                             moe_axis="data" if args.moe else None,
                             moe_aux_coef=0.01 if args.moe else 0.0,
                             output="logits")

    if args.moe or args.pipeline:
        lm = build_scratch()
        print(f"built TransformerLM from scratch "
              f"({'MoE E=' + str(args.moe) if args.moe else 'dense'})")
    elif args.llama:
        import torch
        from transformers import LlamaConfig, LlamaForCausalLM

        from ..interop import load_llama  # reused by the resume step

        torch.manual_seed(0)
        hf = LlamaForCausalLM(LlamaConfig(
            vocab_size=V, hidden_size=32, intermediate_size=48,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=2 * T,
            attention_bias=False, tie_word_embeddings=False)).eval()
        lm = load_llama(hf)
        print("loaded torch Llama weights into TransformerLM "
              "(RMSNorm+RoPE+GQA+SwiGLU)")
    else:
        import torch
        from transformers import GPT2Config, GPT2LMHeadModel

        from ..interop import load_gpt2

        torch.manual_seed(0)
        hf = GPT2LMHeadModel(GPT2Config(
            vocab_size=V, n_positions=2 * T, n_embd=32, n_layer=2,
            n_head=4, attn_pdrop=0.0, embd_pdrop=0.0,
            resid_pdrop=0.0)).eval()
        lm = load_gpt2(hf)
        print("loaded torch GPT-2 weights into TransformerLM")

    # -- 2. fine-tune on a learnable cyclic language -------------------
    r = np.random.RandomState(0)

    def mk(n):
        out = []
        for _ in range(n):
            s = r.randint(1, V + 1)
            seq = [(s + t - 1) % V + 1 for t in range(T + 1)]
            out.append(Sample(np.array(seq[:-1], np.float32),
                              np.array(seq[1:], np.float32)))
        return out

    n_dev = len(jax.devices())
    if args.moe and args.moe % n_dev:
        parser.error(
            f"--moe {args.moe} must be divisible by the data-shard "
            f"count ({n_dev} devices): expert parallelism gives each "
            "shard E/n experts")
    if args.pipeline:
        if n_dev % args.pipeline:
            parser.error(
                f"--pipeline {args.pipeline} must divide the device "
                f"count (have {n_dev}; set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        mesh = Mesh(np.array(jax.devices()).reshape(
            n_dev // args.pipeline, args.pipeline), ("data", "pipe"))
    else:
        mesh = Mesh(np.array(jax.devices()), ("data",))
    # every model here emits LOGITS (load_gpt2 builds output="logits"):
    # pair with the fused CrossEntropyCriterion, which computes its own
    # log-sum-exp — ClassNLL on raw logits would be a garbage objective
    crit = nn.TimeDistributedCriterion(nn.CrossEntropyCriterion(), True)
    ckdir_holder = tempfile.TemporaryDirectory(prefix="modern_lm_ckpt_")
    ckdir = ckdir_holder.name

    import optax

    def fit(model, end_iter):
        opt = DistriOptimizer(model, array(mk(256)), crit,
                              batch_size=32, mesh=mesh)
        opt.set_optim_method(OptaxMethod(optax.adamw, 1e-2,
                                         weight_decay=1e-4))
        opt.set_checkpoint(ckdir, several_iteration(10), format="orbax")
        opt.set_end_when(max_iteration(end_iter))
        opt.optimize()
        return opt

    opt = fit(lm, args.iterations // 2)
    half_loss = opt.optim_method.state["loss"]

    # -- 3. "crash" and resume from the async sharded checkpoint -------
    if args.moe or args.pipeline:
        lm = build_scratch()
    elif args.llama:
        lm = load_llama(hf)
    else:
        lm = load_gpt2(hf)
    opt2 = DistriOptimizer(lm, array(mk(256)), crit, batch_size=32,
                           mesh=mesh)
    opt2.set_optim_method(OptaxMethod(optax.adamw, 1e-2,
                                      weight_decay=1e-4))
    opt2.set_checkpoint(ckdir, several_iteration(10), format="orbax")
    assert opt2.resume_from_checkpoint(), "no checkpoint to resume"
    print(f"resumed from orbax step at iteration "
          f"{opt2.optim_method.state['neval'] - 1} "
          f"(loss was {half_loss:.3f})")
    opt2.set_end_when(max_iteration(args.iterations))
    opt2.optimize()
    print(f"final loss {opt2.optim_method.state['loss']:.3f}")

    # -- 4. generate, then export back to torch ------------------------
    if not (args.moe or args.pipeline or args.llama):
        # GPT-2 heads are bias-free: zero ours BEFORE generating so the
        # framework decode and the torch decode of the export run the
        # SAME parameters (the llama head is born bias-free)
        tree = lm.param_tree()
        head = tree[str(len(lm.modules) - 1)]
        head["bias"] = head["bias"] * 0
        lm.set_param_tree(tree)
    prompt = np.array([[3, 4, 5]], np.int32)
    greedy = np.asarray(lm.generate(prompt, max_new=8))
    sampled = np.asarray(lm.generate(prompt, max_new=8,
                                     rng=jax.random.PRNGKey(0),
                                     temperature=0.8, top_p=0.9))
    print("greedy :", greedy[0].tolist())
    print("nucleus:", sampled[0].tolist())
    want = [(5 + k - 1) % V + 1 for k in range(1, 9)]
    if greedy[0, 3:].tolist() == want:
        print("the fine-tuned model continues the cyclic language "
              "exactly")

    if not (args.moe or args.pipeline):
        import torch

        from ..interop import save_gpt2, save_llama

        hf_out = (save_llama(lm) if args.llama else save_gpt2(lm))
        tp = torch.tensor(prompt.astype(np.int64) - 1)
        back = hf_out.generate(
            tp, max_new_tokens=8, do_sample=False, pad_token_id=0,
            attention_mask=torch.ones_like(tp)).numpy() + 1
        print("torch decode of the export:", back[0].tolist())
        assert back[0, 3:].tolist() == greedy[0, 3:].tolist(), \
            "export diverged from the framework decode"
        print(f"export verified: torch "
              f"{'Llama' if args.llama else 'GPT-2'} reproduces the "
              "framework decode")
    ckdir_holder.cleanup()  # drop the demo's checkpoint tree


if __name__ == "__main__":
    main()
