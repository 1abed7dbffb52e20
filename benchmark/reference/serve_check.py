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
token that the fp8 reference puts first."""
from __future__ import annotations

import inspect
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import common


@partial(jax.jit, static_argnums=(0, 3, 4))
def _logits(ref_cfg, params, ids, head_from: int, mode: str):
    """Logits [B, n, V] of the positions from ``head_from`` on.  A
    reference whose layers are of several kinds names a ``layer``
    argument in its ``block`` and is told which layer this is."""
    ref, cfg_items = ref_cfg
    cfg = dict(cfg_items)
    indexed = "layer" in inspect.signature(ref.block).parameters
    h = ref.embed(params, ids, cfg)
    for i in range(ref.n_layers(cfg)):
        lp = {k.split(".", 2)[2]: v for k, v in params.items()
              if k.startswith(f"h.{i}.")}
        h = (ref.block(lp, h, cfg, mode, layer=i) if indexed
             else ref.block(lp, h, cfg, mode))
    return ref.head(params, h[:, head_from:], cfg, mode)


def _gaps(ref_cfg, params, ids, head_from: int, control: bool, served):
    lg = _logits(ref_cfg, params, ids, head_from, "f32")
    best = jnp.max(lg, -1)
    below = lambda tok: best - jnp.take_along_axis(
        lg, tok[..., None], -1)[..., 0]
    out = {"gap": below(served), "spread": best - jnp.min(lg, -1),
           "agree": jnp.argmax(lg, -1) == served}
    if control:
        low = jnp.argmax(_logits(ref_cfg, params, ids, head_from, "fp8"), -1)
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
    params = common.make_params(ref.param_specs(cfg), ref.n_layers(cfg),
                                cfg["initializer_range"], seed)
    t0, n = prompts0.shape[1], served0.shape[1]
    ids = np.concatenate([prompts0, served0[:, :-1]], 1).astype(np.int32)
    outs = []
    for lo in range(0, len(ids), rows):
        blk = slice(lo, lo + rows)
        pad = rows - len(ids[blk])
        x = np.concatenate([ids[blk], ids[:pad]]) if pad else ids[blk]
        s = served0[blk].astype(np.int32)
        s = np.concatenate([s, served0[:pad].astype(np.int32)]) if pad else s
        o = _gaps((ref, common.hashable(cfg)), params, jnp.asarray(x), t0 - 1,
                  bool(control), jnp.asarray(s))
        outs.append({k: np.asarray(v)[: rows - pad] for k, v in o.items()})
    del params
    return {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}
