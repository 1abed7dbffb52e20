#!/usr/bin/env python3
"""A fingerprint of what each benchmark configuration's CLASS lowers to,
for holding a change against its parent without a chip: the generate
program of every ``benchmark/configs/*.json`` at toy size (its class
and kinds of layer, small widths) and the gradient of the GPT-2 class,
lowered on the CPU, as the sha256 of the text without source locations.

    JAX_PLATFORMS=cpu python tools/program_fingerprint.py [<checkout>]

Run it on two checkouts (``git archive <parent> | tar -x -C
.bench_parent``) and compare the lines: equal lines are programs a
change did not touch.  It sees the CPU's branches only — a kernel arm
chosen on a TPU is held by ``tests/test_tpu_compile.py`` — and nothing
it prints is a measurement.
"""
import hashlib
import importlib
import json
import os
import sys

SMALL = {
    "vocab_size": 128, "embed_dim": 64, "mlp_dim": 128, "num_layers": 2,
    "max_len": 96, "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
    "expert_dim": 32, "n_experts": 8, "top_k": 2, "held": [0, 4],
    "n_shared": 1, "first_dense": 1, "q_rank": 32, "kv_rank": 32,
    "nope_dim": 16, "rope_dim": 8, "v_dim": 24, "window": 16,
    "layer_switch": 2, "mamba_heads": 4, "mamba_head_dim": 16,
    "mamba_d_state": 16, "mamba_groups": 2, "mamba_chunk": 8,
    "layer_types": ["conv", "full_attention", "conv"]}
DROPPED = ("ssm_multipliers", "rope_scaling")


def small(kwargs: dict) -> dict:
    return {k: SMALL.get(k, v) for k, v in kwargs.items()
            if k not in DROPPED}


def main() -> int:
    root = os.path.abspath(sys.argv[1] if len(sys.argv) > 1 else
                           os.path.join(os.path.dirname(__file__), ".."))
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import generate as G

    def digest(lowered) -> str:
        return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]

    models = {}
    for name in sorted(os.listdir("benchmark/configs")):
        with open(os.path.join("benchmark/configs", name)) as f:
            program = json.load(f)["program"]
        mod, cls = program["class"].split(":")
        model = models[name] = getattr(importlib.import_module(mod), cls)(
            **small(program["kwargs"]))
        gen = G.make_generate(model)
        run = [c.cell_contents for c in gen.__closure__
               if hasattr(c.cell_contents, "lower")][0]
        print(name, "generate", digest(run.lower(
            model.param_tree(), jnp.ones((8, 40), jnp.int32), 9,
            jax.random.PRNGKey(0), jnp.float32(0), 0, jnp.float32(1),
            jnp.int32(0), jnp.int32(0), True, False)))
    model = models["gpt2-medium.json"]

    def loss(p, x):
        y = model.apply_fn(p, model.buffer_tree(), x, True,
                           jax.random.PRNGKey(0))[0]
        return jnp.mean(y.astype(jnp.float32) ** 2)

    print("gpt2-medium.json gradient", digest(jax.jit(jax.grad(loss)).lower(
        model.param_tree(), jnp.ones((2, 32), jnp.int32))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
