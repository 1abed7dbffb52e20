#!/usr/bin/env python3
"""A fingerprint of what each benchmark configuration's CLASS lowers to,
for holding a change against its parent without a chip: the programs of
every ``benchmark/configs/*.json`` at toy size (its class and kinds of
layer, small widths) on the CPU — the sampling generate program, beam
search and the int8 cache where the class is served so (``refused``
where it is refused), ``TransformerLM`` through ``PagedDecoder`` (prefill
and decode, with and without ``page_window``) and the gradient of the
GPT-2 class.  Two digests a program:

* ``text``: sha256 of the lowered text WITHOUT source locations — what
  is computed;
* ``scopes``: sha256 of the sorted multiset of ``op_name`` metadata in
  the CPU-compiled HLO (no file, no line) — where ``jax.named_scope``
  lives, which the text cannot see: a device scope that moved, opened
  around other operations or changed its nesting moves this one.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/program_fingerprint.py [<checkout>] [--json]

(the settings ``tests/conftest.py`` runs tier-1 with).  ``--json`` prints
one object, ``{"<config> <program>": {"text", "scopes"}}``:
``tests/recorded/program_fingerprints.json`` is that output, and
``tests/test_program_fingerprint.py`` holds the tree to it a line a case.
A PR that MEANS to change a program records again with this command and
says in ``CHANGES.md`` which lines moved.  It sees the CPU's branches
only — a kernel arm chosen on a TPU is held by
``tests/test_tpu_compile.py`` — and nothing it prints is a measurement.
"""
import hashlib
import importlib
import json
import os
import re
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
_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: a page of the toy arena; the toy prompt is five of them, so a window
#: of two pages beside one anchor binds in prefill and in decode
PAGE = 8


def small(kwargs: dict) -> dict:
    return {k: SMALL.get(k, v) for k, v in kwargs.items()
            if k not in DROPPED}


def digests(lowered) -> dict:
    def sha(text):
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    scopes = sorted(_OP_NAME.findall(lowered.compile().as_text()))
    return {"text": sha(lowered.as_text()), "scopes": sha("\n".join(scopes))}


def _jitted(fn):
    """The jitted program a ``make_*`` builder's function closes over."""
    return [c.cell_contents for c in fn.__closure__
            if hasattr(c.cell_contents, "lower")][0]


def programs(root: str) -> dict:
    """``{"<config> <program>": thunk}``; a thunk lowers its program
    (one toy model a configuration, built on first use) and returns
    :func:`digests` of it, or ``{"refused": <exception class>}`` where
    the builder refuses the class."""
    if root not in sys.path:
        sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import generate as G

    configs = os.path.join(root, "benchmark", "configs")
    models = {}

    def model_of(name):
        if name not in models:
            with open(os.path.join(configs, name)) as f:
                program = json.load(f)["program"]
            mod, cls = program["class"].split(":")
            models[name] = getattr(importlib.import_module(mod), cls)(
                **small(program["kwargs"]))
        return models[name]

    def refusable(build):
        def thunk():
            try:
                lowered = build()
            except TypeError as e:
                return {"refused": type(e).__name__}
            return digests(lowered)
        return thunk

    def sampling(name, **kw):
        def build():
            model = model_of(name)
            return _jitted(G.make_generate(model, **kw)).lower(
                model.param_tree(), jnp.ones((8, 40), jnp.int32), 9,
                jax.random.PRNGKey(0), jnp.float32(0), 0, jnp.float32(1),
                jnp.int32(0), jnp.int32(0), True, False)
        return refusable(build)

    def beam(name):
        def build():
            model = model_of(name)
            return _jitted(G.make_beam_search(model)).lower(
                model.param_tree(), jnp.ones((2, 40), jnp.int32), 5, 3,
                jnp.int32(0), jnp.int32(0))
        return refusable(build)

    def paged(name, which, window):
        def build():
            from bigdl_tpu.serving.kvpool import KVPagePool

            model = model_of(name)
            if transformer(name):
                pool = KVPagePool.for_model(model, 12, page_size=PAGE)
            else:       # refused before its geometry is looked at
                pool = KVPagePool(12, len(model.modules) - 3,
                                  SMALL["num_kv_heads"], PAGE,
                                  SMALL["head_dim"])
            dec = G.PagedDecoder(model, pool, page_window=window)
            shape = (pool.num_pages, pool.layers, pool.num_kv_heads, PAGE,
                     pool.head_dim)
            arena = jax.ShapeDtypeStruct(shape, jnp.float32)
            pt = jnp.zeros((8,), jnp.int32)
            if which == "prefill":
                return dec._prefill_fn.lower(
                    model.param_tree(), jnp.ones((1, 5 * PAGE), jnp.int32),
                    pt, arena, arena)
            return dec._decode_fn.lower(
                model.param_tree(), jnp.ones((1, 1), jnp.int32),
                jnp.int32(5 * PAGE), pt, arena, arena)
        return refusable(build)

    def gradient(name):
        def build():
            model = model_of(name)

            def loss(p, x):
                y = model.apply_fn(p, model.buffer_tree(), x, True,
                                   jax.random.PRNGKey(0))[0]
                return jnp.mean(y.astype(jnp.float32) ** 2)

            return jax.jit(jax.grad(loss)).lower(
                model.param_tree(), jnp.ones((2, 32), jnp.int32))
        return refusable(build)

    def transformer(name):
        with open(os.path.join(configs, name)) as f:
            return json.load(f)["program"]["class"].endswith(
                ":TransformerLM")

    out = {}
    for name in sorted(os.listdir(configs)):
        out[f"{name} generate"] = sampling(name)
        out[f"{name} generate_int8"] = sampling(name, kv_dtype="int8")
        out[f"{name} beam"] = beam(name)
        if not transformer(name):
            out[f"{name} paged"] = paged(name, "decode", None)
            continue
        for which in ("prefill", "decode"):
            out[f"{name} paged_{which}"] = paged(name, which, None)
            out[f"{name} paged_{which}_window"] = paged(name, which, 2)
    out["gpt2-medium.json gradient"] = gradient("gpt2-medium.json")
    return out


def main() -> int:
    args = [a for a in sys.argv[1:] if a != "--json"]
    root = os.path.abspath(args[0] if args else
                           os.path.join(os.path.dirname(__file__), ".."))
    os.chdir(root)
    found = {key: thunk() for key, thunk in programs(root).items()}
    if "--json" in sys.argv[1:]:
        print(json.dumps(found, indent=1, sort_keys=True))
    else:
        for key, d in found.items():
            print(key, *(f"{k}={v}" for k, v in d.items()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
