"""Generate docs/api-reference.md from the LIVE registry.

The reference's user-facing API surface is its layer/criterion class
list (nn/, 142 classes) plus optim methods, triggers, validation
methods, data transforms and the create* Python bridge
(pyspark PythonBigDL.scala).  This walks the same live objects the
``bigdl_tpu.api`` reflection facade serves, so the generated page can
never drift from the code.

Run:  JAX_PLATFORMS=cpu python tools/gen_api_reference.py
"""
import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")


def first_line(obj):
    doc = inspect.getdoc(obj) or ""
    line = doc.split("\n", 1)[0].strip()
    return line


def sig(cls):
    try:
        s = str(inspect.signature(cls.__init__))
        s = s.replace("(self, ", "(").replace("(self)", "()")
        return s if len(s) <= 90 else s[:87] + "...)"
    except (TypeError, ValueError):
        return "(...)"


def table(names, lookup):
    out = ["| Name | Constructor | Summary |", "|---|---|---|"]
    for n in names:
        cls = lookup(n)
        out.append(f"| `{n}` | `{sig(cls)}` | {first_line(cls)} |")
    return "\n".join(out)


def main():
    from bigdl_tpu import api, nn
    from bigdl_tpu import optim
    from bigdl_tpu.nn.module import AbstractModule
    from bigdl_tpu.nn.criterion import AbstractCriterion
    from bigdl_tpu.optim.optim_method import OptimMethod
    from bigdl_tpu.optim.validation import ValidationMethod

    reg = {n: api._REGISTRY[n] for n in api.layer_names()}
    layers = sorted(n for n, c in reg.items()
                    if isinstance(c, type) and issubclass(c, AbstractModule))
    crits = sorted(n for n, c in reg.items()
                   if isinstance(c, type) and issubclass(c, AbstractCriterion))
    other = sorted(set(reg) - set(layers) - set(crits))

    optims = sorted(n for n in dir(optim)
                    if isinstance(getattr(optim, n), type)
                    and issubclass(getattr(optim, n), OptimMethod)
                    and getattr(optim, n) is not OptimMethod)
    vmethods = sorted(
        n for n in dir(optim)
        if isinstance(getattr(optim, n), type)
        and issubclass(getattr(optim, n), ValidationMethod)
        and getattr(optim, n) is not ValidationMethod)

    doc = ["# API reference (generated — do not edit)",
           "",
           "Regenerate with `python tools/gen_api_reference.py`.  Every",
           "name below is constructible three ways, matching the",
           "reference Python bridge: `bigdl_tpu.nn.Linear(...)`,",
           "`api.create('Linear', ...)`, `api.createLinear(...)`.",
           "",
           f"## Layers ({len(layers)})", "",
           table(layers, lambda n: reg[n]), "",
           f"## Criterions ({len(crits)})", "",
           table(crits, lambda n: reg[n]), ""]
    if other:
        doc += [f"## Other registry entries ({len(other)})", "",
                table(other, lambda n: reg[n]), ""]
    doc += [f"## Optimization methods ({len(optims)})", "",
            table(optims, lambda n: getattr(optim, n)), "",
            f"## Validation methods ({len(vmethods)})", "",
            table(vmethods, lambda n: getattr(optim, n)), "",
            "## Triggers", "",
            "`every_epoch()`, `every_iteration()`, `several_iteration(n)`,",
            "`max_epoch(n)`, `max_iteration(n)`, `min_loss(x)`,",
            "`max_score(x)`, `and_(..)`, `or_(..)` —",
            "see `bigdl_tpu.optim.trigger`.", "",
            "## Module-wide behaviour", "",
            "- **Gradient buffers appear on first use.** A module owns no",
            "  gradient buffer when it is built: `grad_tree()`,",
            "  `parameters()`, `zero_grad_parameters()` and `backward()`",
            "  make the zeros they need, so a model that is only served, or",
            "  trained through an optimizer's compiled step, never pays",
            "  for them.",
            "- **The decode-state protocol** (PR 45; `nn/attention.py`): what",
            "  a layer keeps between tokens lives with the layer.  An",
            "  operator answers `state_init` / `sequence` / `step`",
            "  (`MultiHeadAttention`, `LatentAttention`, `Mamba2Mixer`,",
            "  `GatedShortConv`), a block `state_init` / `advance`",
            "  (`TransformerBlock`, `HybridMambaBlock`, `ParallelMoEBlock`,",
            "  `SequentialMoEBlock`), and the four LMs share ONE",
            "  `generate()` from `models.generate.CausalLM`;",
            "  `models/generate.py` asks them and names no architecture",
            "  (docs/serving.md, \"What a new architecture brings to be",
            "  served\").",
            "- **`param_dtype`** (`models.hybrid_mamba.HybridMambaLM`): the",
            "  dtype the model HOLDS its floating parameters in.  The",
            "  constructor draws in it, `set_param_tree` casts each",
            "  incoming leaf to it (a leaf already there is kept as it",
            "  is), and a generator whose compute dtype equals it casts",
            "  nothing inside the call.  `A_log`, `dt_bias`, `D` and the",
            "  SSM state compute in float32 whatever the held dtype.",
            "- **`HybridMambaLM`** is a `Container` with `TransformerLM`'s",
            "  child layout (`0` embedding, `1..L` `HybridMambaBlock`s,",
            "  `L+1` RMSNorm, `L+2` head); `generate()` /",
            "  `InferenceServer.submit_generate` decode it through a K/V",
            "  cache and a recurrent state per layer; the paged path",
            "  (`PagedDecoder`) refuses it.",
            "- **`models.parallel_moe.ParallelMoELM`** (PR 32; also",
            "  `param_dtype` and the device draw): a `Container` of",
            "  `ParallelMoEBlock`s — ONE bias-free `LayerNorm`",
            "  (`with_bias=False`), then attention and a `DroplessMoE` on",
            "  that same normed input, both summed into the residual —",
            "  whose layers are `\"sliding\"` (window, interleaved RoPE:",
            "  `MultiHeadAttention(rope=\"interleaved\", window=W)`) or",
            "  `\"full\"` (no positions, `rope=None`) by `layer_switch` /",
            "  `local_first`, and whose head (`TiedHead`, child `L+2`)",
            "  owns no leaf and reads the embedding's.  `generate()` /",
            "  `submit_generate` keep a cache per layer of that layer's own",
            "  length (`min(T_cache, window)` for a sliding layer);",
            "  `PagedDecoder` refuses it.",
            "- **`nn.LatentAttention`** (PR 36): multi-head LATENT attention",
            "  (MLA) — `q` and K/V through low-rank bottlenecks (`q_rank`,",
            "  `kv_rank`, each with an RMSNorm), the rotated part of the",
            "  key (`rope_dim`) ONE vector a position shared by all heads,",
            "  a query/key head of `nope_dim + rope_dim` and a value head",
            "  of `v_dim`.  Seven leaves: `wq_a`, `q_norm`, `wq_b`,",
            "  `wkv_a`, `kv_norm`, `wkv_b`, `wo`.  `apply_fn` is the",
            "  EXPANDED full-sequence form (dense or flash causal",
            "  attention), differentiable by autodiff.",
            "- **`nn.GatedShortConv(embed_dim, kernel=3)`** (PR 40): the",
            "  gated short convolution of `lfm2` / `lfm2_moe` — `(B, C, u) =",
            "  split(w_in x)`, a depthwise causal convolution of `kernel`",
            "  taps over `B * u` (`nn.mamba.causal_conv`), `w_out (C * .)`,",
            "  no bias.  Leaves `w_in` `[3D, D]`, `conv` `[kernel, D]`,",
            "  `w_out` `[D, D]`.  `apply_fn` is the whole sequence;",
            "  `sequence` / `step` / `state_init` are `Mamba2Mixer`'s",
            "  contract over the one state it has: `{\"conv\": [batch,",
            "  kernel - 1, D]}`, whatever the context.  No K, no V.",
            "- **`nn.MultiHeadAttention(qk_norm=False, norm_eps=1e-6)`**",
            "  (PR 40): with `qk_norm`, leaves `q_norm` / `k_norm`",
            "  `[head_dim]` and an RMSNorm over the numbers of EACH query",
            "  and key head (one gain vector for all heads) before the",
            "  rotation, in every `seq_strategy` (`normed_heads`, which the",
            "  cached and the paged decoder call too: a cache holds normed,",
            "  rotated keys).  Off by default: no leaf, no operation.",
            "- **`models.latent_moe.SequentialMoELM(vocab_size, embed_dim,",
            "  operators, ffns, tied_head=False, ...)`** (PR 40; `param_dtype`",
            "  and the device draw): the `Container` of SEQUENTIAL pre-norm",
            "  blocks (`SequentialMoEBlock`: RMSNorm, operator, RMSNorm, FFN;",
            "  `LatentMoEBlock` is its older name) with ONE factory a layer",
            "  for the operator (`LatentAttention`, `MultiHeadAttention` or",
            "  `GatedShortConv`) and one for the FFN (`GatedFFN` or",
            "  `DroplessMoE`), and a `LogitHead` that gives float32 logits",
            "  from a matrix of its own or (`tied_head`: it owns no leaf,",
            "  `parallel_moe.TiedHeadTrees` leaves its entry out of the",
            "  trees) from the embedding's.  Two families are constructors",
            "  of it and add no method:",
            "- **`models.latent_moe.ShortConvMoELM`** (PR 40; `lfm2_moe`,",
            "  LFM2-24B-A2B): `layer_types[i]` `\"conv\"` or",
            "  `\"full_attention\"` (grouped-query, `qk_norm=True`, rotation",
            "  by halves), a dense FFN in the first `first_dense` layers and",
            "  experts after them (`score_bias=True`, `renorm_eps=1e-6`, no",
            "  shared expert, `held` default all), a tied head.",
            "  `generate()` / `submit_generate` keep K/V for the attention",
            "  layers ONLY and a two-position tail for each conv layer",
            "  (`cache_footprint`: `kv_cache_bytes` sums the layers that",
            "  keep K/V, the tails are `recurrent_state_bytes`);",
            "  `kv_dtype=\"int8\"` quantises those K/V and leaves the tails;",
            "  `PagedDecoder` refuses the block by name.",
            "- **`models.latent_moe.PreRoutedMoELM`** (PR 46;",
            "  `smallthinker`, SmallThinker-21BA3B-Instruct): grouped-query",
            "  attention without biases or QK-norm, rotated by halves where",
            "  `rope_layout[i]` and under a sliding `window` where",
            "  `window_layout[i]` (a layer with neither is global and has no",
            "  positions), an expert layer in EVERY block whose router",
            "  reads the block's INPUT (`SequentialMoEBlock(pre_routed=True)`",
            "  -> `DroplessMoE.routed(..., scores_from=x)`), a softmax over",
            "  the chosen logits (`scoring=\"softmax\"`, `renormalize`),",
            "  ReLU-gated experts (`DroplessMoE(activation=\"relu\")`), no",
            "  shared expert, an untied head.  `generate()` /",
            "  `submit_generate` keep a ring of `window` positions for a",
            "  window layer and every position for a global one",
            "  (`cache_footprint`: `kv_cache_bytes_window` /",
            "  `kv_cache_bytes_full`, from `MultiHeadAttention.footprint`);",
            "  a bucket's prompt of more than 65 536 tokens goes through the",
            "  blocks in groups of rows (`generate.prefill_groups`);",
            "  `kv_dtype=\"int8\"` and beam search serve it, `PagedDecoder`",
            "  refuses the block by name.",
            "- **`models.latent_moe.LatentMoELM`** (PR 36; the same",
            "  container since PR 40, its tree and its programs unchanged):",
            "  `LatentAttention` in every layer, a dense SwiGLU (`GatedFFN`)",
            "  for the first `first_dense` layers and a `DroplessMoE` after",
            "  them (`score_bias=True`, `routed_scale`), an untied head.",
            "  `generate()` / `submit_generate` keep the latent and the",
            "  shared rotated key of every position (`ckv`, `kr`) and",
            "  nothing by head: prefill expands them once, a decode step",
            "  absorbs `wkv_b` into the query and the output and attends",
            "  through `ops.latent_attend.latent_attend` (PR 37: one",
            "  Pallas kernel over the written part of the cache where a",
            "  layer's cache is large, on a TPU; the plain einsums",
            "  elsewhere — `attend_plan` decides by shapes).",
            "  `PagedDecoder` and `kv_dtype=\"int8\"` refuse it by name.",
            "- **`ops.gqa_attend.attend_plan(B, Hkv, T, Dh, dtype, Tq=1,",
            "  window=None)`** (PR 41): the arm of a decode step's attend",
            "  on per-head K/V — one query a row against the un-repeated",
            "  `[B, Hkv, T, Dh]` leaves, every model above but the latent",
            "  one; the decode step (`nn.MultiHeadAttention.step`) and",
            "  `cache_footprint` read the one rule.  Where `attend_plan`",
            "  says so (a TPU, `Tq == 1`, a cache contiguous from position",
            "  0 and no ring, K/V stored floating, `T` a whole number of",
            "  128-position blocks, a head of 64 or of whole lane tiles,",
            "  and a layer's K + V of `KERNEL_MIN_CACHE_BYTES` or more) ONE",
            "  Pallas kernel walks the cache up to the block `pos` falls",
            "  in; elsewhere `ops.gqa_attend.gqa_attend_reference`, the plain einsums and",
            "  the kernel's reference.  `cache_footprint` / `serve.dispatch`",
            "  say which: `kv_attend`, `kv_attend_block`.",
            "- **`parallel.moe.route_top_k(..., select_bias=None,",
            "  gate_scale=1.0, renorm_eps=1e-20)`** /",
            "  **`DroplessMoE(score_bias=False, routed_scale=1.0,",
            "  renorm_eps=1e-20)`**: the `noaux_tc` router — a per-expert",
            "  bias (leaf `score_bias`, float32 whatever the model holds",
            "  or computes in: `nn.module.FLOAT32_LEAVES`) added to the",
            "  scores for the SELECTION only; the gates are the unbiased",
            "  scores of the chosen, renormalised (`renorm_eps` in the",
            "  sum: `lfm2_moe` has 1e-6), times `routed_scale`.",
            "  The defaults are the router as it was.",
            "- **`parallel.moe.DroplessMoE`**: scores ALL `n_experts`",
            "  (`softmax` or `sigmoid`, float32), keeps `top_k`, and",
            "  computes the part of the mixture the experts it HOLDS give",
            "  (`held=(first, count)`; default all) plus the mean of",
            "  `n_shared` shared experts.  Nothing is dropped: assignments",
            "  are sorted by expert into a buffer sized for the worst",
            "  imbalance and go through one grouped matrix product per",
            "  projection (`grouped_matmul`: `jax.lax.ragged_dot`; for a",
            "  decode step's buffer of at most 2048 rows on a TPU the",
            "  repo's own kernel, `ops.grouped_decode.grouped_decode` —",
            "  one grid step an expert, its whole matrix ONE contiguous",
            "  tile of 3.9-8 MB, so a hit expert's weights cross HBM",
            "  once wherever its rows lie, the buffer walked in products",
            "  of 128 rows or of 64 where 128 does not divide it",
            "  (SmallThinker's 192) — for a larger matrix the Pallas",
            "  grouped matmul that ships with jax, and for a piece of a",
            "  prompt pass (more than 2048 rows) whose expert matrix is",
            "  one tile `ops.grouped_prefill.grouped_prefill`: a (row",
            "  tile, group) visit a grid step with ONE k tile, a group's",
            "  matrix fetched once and the next group's behind it;",
            "  `grouped_plan` is the one rule, from shapes alone, and",
            "  `cache_footprint` / `serve.dispatch` say which: `grouped`,",
            "  `grouped_tiles`, `grouped_tiles_down` for the decode step,",
            "  `grouped_prefill`, `grouped_prefill_tiles`,",
            "  `grouped_prefill_tiles_down` for a piece of the prompt",
            "  pass).  `MoEFFN` keeps its capacity",
            "  dispatch for training; decode advances it through the same",
            "  dropless dispatch.",
            "- **`ops.flash_attention(..., window=W)`**: the FORWARD kernel",
            "  skips the sub-tiles wholly older than the window and masks",
            "  those its edge crosses; a window of the sequence or more is",
            "  the causal schedule; differentiating it raises.",
            "- **`nn.initialization.device_draw()`**: inside this context",
            "  the random initialisers (`RandomUniform`, `RandomNormal`,",
            "  `Xavier`, `MsraFiller`) draw with `jax.random` on the",
            "  device (its own bit generator, `impl=\"rbg\"`), seeded from",
            "  the host generator's stream — the same",
            "  distributions, other numbers.  `HybridMambaLM` builds and",
            "  resets under it (2.4 B weights from the host's Mersenne",
            "  Twister cost most of a minute of every start); every other",
            "  model keeps the host stream and its historical numbers.",
            ""]

    out_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "api-reference.md")
    with open(out_path, "w") as f:
        f.write("\n".join(doc))
    print(f"wrote {out_path}: {len(layers)} layers, {len(crits)} "
          f"criterions, {len(optims)} optim methods")


if __name__ == "__main__":
    main()
