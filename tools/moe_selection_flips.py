#!/usr/bin/env python3
"""How often the served program and the float32 reference pick
DIFFERENT experts for a token — chip only, at a configuration's real
widths, on seeded weights.

    python tools/moe_selection_flips.py [--config command-a-plus-l4e16v8]
        [--seed 7 --rows 16 --positions 256]

The selection is discrete: where a token's k-th and (k+1)-th router
scores lie within bfloat16's error, the program (bfloat16 weights and
residual stream, float32 scores) and the reference (float32 at HIGHEST)
keep different experts, and that token's outputs differ by a whole
expert's part, not by rounding.  This reads, layer by layer over the
same token ids, the share of (token, layer) pairs whose top-k SETS
differ, and the share whose difference touches an expert HELD here (the
only ones that move this chip's result).  Both go the whole depth on
their own hidden states, as in a served run.  PERF.md §6 "PR 32".
"""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="command-a-plus-l4e16v8")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--positions", type=int, default=256)
    ap.add_argument("--config-file", default=None,
                    help="a configuration file by path (a toy, for a "
                         "CPU rehearsal of the control flow)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run without a TPU: NOT a measurement")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import program
    from benchmark.reference import common
    from bigdl_tpu.parallel.moe import route_top_k

    if jax.devices()[0].platform != "tpu" and not args.rehearse_cpu:
        print("needs a TPU", file=sys.stderr)
        return 2
    with open(args.config_file or os.path.join(
            ROOT, "benchmark", "configs", args.config + ".json")) as fh:
        cfg = json.load(fh)
    ref = program.reference_for(cfg)
    L, first, held = ref.n_layers(cfg), cfg["first_expert_held"], \
        cfg["num_experts"]
    ids = np.random.RandomState(args.seed).randint(
        1, cfg["vocab_size"] + 1, (args.rows, args.positions))

    # the program: its own blocks on its own bfloat16 hidden states
    model = program.build_model(cfg, args.seed, ref=ref)
    tree = model.param_tree()
    picked = []

    def block(i_params, h, i):
        blk = model.modules[1 + i]
        n, _ = blk.modules[0].apply_fn(i_params["0"], {}, h, False, None)
        moe = blk.moe
        _, idx = route_top_k(n.reshape(-1, n.shape[-1]),
                             i_params["2"]["router_w"], None, moe.top_k,
                             moe.scoring, moe.renormalize)
        out, _ = blk.apply_fn(i_params, blk.buffer_tree(), h, False, None)
        return out, idx

    block = jax.jit(block, static_argnums=2)
    h, _ = model.modules[0].apply_fn(tree["0"], {}, jnp.asarray(ids), False,
                                     None)
    for i in range(L):
        h, idx = block(tree[str(1 + i)], h, i)
        picked.append(np.asarray(idx))
    for leaf in jax.tree_util.tree_leaves(tree):
        leaf.delete()
    del model, tree, h
    gc.collect()

    # the reference: float32, one layer's leaves at a time
    specs, std = ref.param_specs(cfg), cfg["initializer_range"]
    key = common.hashable(cfg)
    emb = common.seeded_leaf(args.seed, "embed", *specs["top"]["embed"], std)
    hr = ref.embed({"embed": emb}, jnp.asarray(ids - 1), cfg)
    del emb

    def ref_block(lp, h, layer):
        cfg_ = dict(key)
        n = ref._ln(h, lp["input_norm"], cfg_["layer_norm_eps"])
        return (ref.block(lp, h, cfg_, "f32", layer=layer),
                ref.select(lp, n, cfg_)[1])

    ref_block = jax.jit(ref_block, static_argnums=2)
    rows = []
    for i in range(L):
        lp = {n: common.seeded_leaf(args.seed, f"h.{i}.{n}", s, k, std)
              for n, (s, k) in specs["layer"].items()}
        outs = [ref_block(lp, hr[r:r + 4], i)
                for r in range(0, args.rows, 4)]
        hr = jnp.concatenate([o[0] for o in outs])
        want = np.concatenate([np.asarray(o[1]).reshape(
            -1, picked[i].shape[-1]) for o in outs])
        del lp
        got = picked[i]
        tokens = got.shape[0]
        differ = touch = 0
        for a, b in zip(got, want):
            d = set(a.tolist()) ^ set(b.tolist())
            differ += bool(d)
            touch += any(first <= e < first + held for e in d)
        rows.append({"layer": i, "tokens": tokens,
                     "sets_differ_pct": 100.0 * differ / tokens,
                     "touch_held_pct": 100.0 * touch / tokens})
        print(json.dumps(rows[-1]), flush=True)
    total = sum(r["tokens"] for r in rows)
    print(json.dumps({
        "config": args.config, "seed": args.seed, "pairs": total,
        "sets_differ_pct": sum(r["sets_differ_pct"] * r["tokens"]
                               for r in rows) / total,
        "touch_held_pct": sum(r["touch_held_pct"] * r["tokens"]
                              for r in rows) / total}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
