"""Teacher-forced comparison of served greedy tokens with the plain
reference: run the reference ONCE over each prompt with its served
tokens and read, at every served position, by how much the served
token's logit lies below the reference's best.  A sound bfloat16 server
picks the reference's best token or one within rounding of it; a server
computing in a lower precision, or feeding back a wrong token, or
reading a wrong cache slot, does not.

The control (``control=True``; never run by the benchmark's own runs)
puts the reference in the program's place at the lower precision: at
each position of the same prompts and tokens it reads the gap of the
token that the fp8 reference puts first.

The reference's float32 weights exist ONE LAYER AT A TIME: layers are
the outer loop and blocks of rows the inner one, the hidden states of
all checked sequences are kept between layers, and each layer's leaves
— and each top-level leaf ``embed`` or ``head`` reads — are seeded, used
for every block and dropped.  So the check costs one float32 layer
beside ``[N, T, d]`` of hidden states, whatever the depth."""
from __future__ import annotations

import inspect
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import common


class _Reads(dict):
    """A parameter dict that notes which leaves are read as ``p[name]``."""

    def __init__(self, leaves):
        super().__init__(leaves)
        self.read = set()

    def __getitem__(self, name):
        self.read.add(name)
        return super().__getitem__(name)


def _top_leaves(fn, top_specs: dict, seed: int, std: float, x) -> dict:
    """The top-level leaves ``fn(p, x)`` reads as ``p[name]`` (found by
    tracing it on shapes; ``x`` is a shape too), seeded."""
    shapes = {n: jax.ShapeDtypeStruct(tuple(s), jnp.float32)
              for n, (s, _) in top_specs.items()}
    read = set()

    def traced(p, x):
        p = _Reads(p)
        out = fn(p, x)
        read.update(p.read)
        return out

    jax.eval_shape(traced, shapes, x)
    return {n: common.seeded_leaf(seed, n, *top_specs[n], std)
            for n in top_specs if n in read}


@partial(jax.jit, static_argnums=(0,))
def _embed(ref_cfg, top, ids):
    ref, cfg_items = ref_cfg
    return ref.embed(top, ids, dict(cfg_items))


@partial(jax.jit, static_argnums=(0, 3, 4))
def _block(ref_cfg, lp, h, mode: str, layer):
    """One layer over one block of rows.  A reference whose layers are
    of several kinds names a ``layer`` argument in its ``block`` and is
    told which layer this is (``layer`` is None for the others, so one
    program serves every layer)."""
    ref, cfg_items = ref_cfg
    if layer is None:
        return ref.block(lp, h, dict(cfg_items), mode)
    return ref.block(lp, h, dict(cfg_items), mode, layer=layer)


@partial(jax.jit, static_argnums=(0, 5))
def _gaps(ref_cfg, top, h, h_low, served, head_from: int):
    """``h`` [B, T, d] after the last layer -> per-token readings of the
    positions from ``head_from`` on; ``h_low`` is the fp8 control's
    hidden state, or None."""
    ref, cfg_items = ref_cfg
    cfg = dict(cfg_items)
    lg = ref.head(top, h[:, head_from:], cfg, "f32")
    best = jnp.max(lg, -1)
    below = lambda tok: best - jnp.take_along_axis(
        lg, tok[..., None], -1)[..., 0]
    out = {"gap": below(served), "spread": best - jnp.min(lg, -1),
           "agree": jnp.argmax(lg, -1) == served}
    if h_low is not None:
        low = jnp.argmax(ref.head(top, h_low[:, head_from:], cfg, "fp8"), -1)
        out["control_gap"] = below(low)
    return out


def teacher_forced(ref, cfg: dict, seed: int, prompts0: np.ndarray,
                   served0: np.ndarray, control: bool = False,
                   rows: int = 4) -> dict:
    """``prompts0`` [N, T0] and ``served0`` [N, n], 0-based ids of equal
    lengths.  Returns per-token arrays [N, n]: ``gap`` (reference best
    minus the served token's logit), ``spread``, ``agree`` and, for the
    control, ``control_gap``.  Weights come from the seed, never from
    the program; sequences go through in blocks of ``rows``."""
    specs, std = ref.param_specs(cfg), cfg["initializer_range"]
    key = (ref, common.hashable(cfg))
    indexed = "layer" in inspect.signature(ref.block).parameters
    t0, n_seq = prompts0.shape[1], len(prompts0)
    ids = np.concatenate([prompts0, served0[:, :-1]], 1).astype(np.int32)
    served = served0.astype(np.int32)
    pad = -n_seq % rows        # the last block is filled from the first rows
    if pad:
        ids = np.concatenate([ids, ids[:pad]])
        served = np.concatenate([served, served[:pad]])
    blocks = [slice(lo, lo + rows) for lo in range(0, len(ids), rows)]

    top = _top_leaves(lambda p, x: ref.embed(p, x, cfg), specs["top"], seed,
                      std, jax.ShapeDtypeStruct((rows, ids.shape[1]),
                                                jnp.int32))
    h = [_embed(key, top, jnp.asarray(ids[b])) for b in blocks]
    modes = {"f32": h, "fp8": list(h)} if control else {"f32": h}
    jax.block_until_ready(h)
    del top
    for i in range(ref.n_layers(cfg)):
        lp = {name: common.seeded_leaf(seed, f"h.{i}.{name}", shape, kind,
                                       std)
              for name, (shape, kind) in specs["layer"].items()}
        for mode, hs in modes.items():
            for j in range(len(hs)):
                hs[j] = _block(key, lp, hs[j], mode, i if indexed else None)
        # the layer's leaves go before the next layer's are made
        jax.block_until_ready(modes)
        del lp
    top = _top_leaves(lambda p, x: ref.head(p, x, cfg, "f32"), specs["top"],
                      seed, std, jax.ShapeDtypeStruct(h[0].shape,
                                                      jnp.float32))
    outs, low = [], modes.get("fp8")
    for j, b in enumerate(blocks):
        o = _gaps(key, top, h[j], low and low[j], jnp.asarray(served[b]),
                  t0 - 1)
        outs.append({k: np.asarray(v) for k, v in o.items()})
        for hs in modes.values():       # a block's states go as it is read
            hs[j] = None
    return {k: np.concatenate([o[k] for o in outs])[:n_seq] for k in outs[0]}
