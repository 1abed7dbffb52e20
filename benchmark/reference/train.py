"""The first optimizer steps of a training job, followed by the plain
reference: mean token cross-entropy, its gradient by ``jax.grad`` of the
float32 forward, and Adam as published (Kingma & Ba 2015, with bias
correction, epsilon outside the root).  The batch goes through in
blocks of rows (the gradient of a mean is the mean of the blocks'), and
each block is rematerialised layer by layer under ``lax.scan``, so the
whole thing fits beside nothing else on one chip.

It returns what the comparison needs and nothing larger: each step's
loss, the norm of every leaf of the FIRST gradient, and the parameters
after the last step (on the device, stacked by layer)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import common


def _loss_sum(ref, cfg, mode, params, x, y):
    top = {k: v for k, v in params.items() if not k.startswith("h.")}
    stack = {k[2:]: v for k, v in params.items() if k.startswith("h.")}

    @jax.checkpoint
    def body(h, lp):
        return ref.block(lp, h, cfg, mode), None

    h, _ = jax.lax.scan(body, ref.embed(top, x, cfg), stack)
    return common.token_xent_sum(ref.head(top, h, cfg, mode), y)


@partial(jax.jit, static_argnums=(0, 1))
def _block_grad(ref_cfg, mode, params, x, y):
    ref, cfg_items = ref_cfg
    return jax.value_and_grad(
        lambda p: _loss_sum(ref, dict(cfg_items), mode, p, x, y))(params)


@partial(jax.jit, donate_argnums=(0,))
def _acc(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


@partial(jax.jit, donate_argnums=(0, 1, 2), static_argnums=(5, 6, 7))
def _adam(params, m, v, g, t, lr, b1, b2, eps=1e-8):
    m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                               v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, m_, v_: p - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps),
        params, m, v)
    return params, m, v


@jax.jit
def _leaf_norms(tree):
    """Norm of every leaf; a leaf stacked by layer gives one per layer."""
    return {k: (jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))
                if k.startswith("h.") else jnp.sqrt(jnp.sum(a * a)))
            for k, a in tree.items()}


def flat_norms(norms: dict) -> dict:
    """{'h.name': [L]} -> {'h.<i>.name': float}, top leaves unchanged."""
    out = {}
    for k, v in norms.items():
        v = np.asarray(v)
        if k.startswith("h."):
            out.update({f"h.{i}.{k[2:]}": float(x) for i, x in enumerate(v)})
        else:
            out[k] = float(v)
    return out


def run_steps(ref, cfg: dict, seed: int, batches, lr: float,
              b1: float = 0.9, b2: float = 0.999, rows: int = 2,
              mode: str = "f32", devices=None) -> dict:
    """Follow ``batches`` [(x, y) int arrays [B, T], 0-based] from the
    seeded weights.  Returns losses, the first gradient's leaf norms and
    the final parameters (stacked, on the device).  With several
    ``devices`` each block of rows is split over them (parameters
    replicated, the compiler adds the sum), which only shortens the
    wait: ``rows`` is then per device."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = list(devices or jax.devices()[:1])
    mesh = Mesh(np.array(devices), ("rows",))
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("rows"))
    rows *= len(devices)
    specs = ref.param_specs(cfg)
    make = lambda: jax.device_put(common.make_params(
        specs, ref.n_layers(cfg), cfg["initializer_range"], seed,
        stacked=True), whole)
    params = make()
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    key = (ref, common.hashable(cfg))
    losses, first = [], None
    for t, (x, y) in enumerate(batches, start=1):
        n_tok = x.shape[0] * x.shape[1]
        g, total = None, 0.0
        for lo in range(0, x.shape[0], rows):
            ls, gb = _block_grad(
                key, mode, params,
                jax.device_put(np.asarray(x[lo:lo + rows], np.int32), split),
                jax.device_put(np.asarray(y[lo:lo + rows], np.int32), split))
            total += float(ls)
            g = gb if g is None else _acc(g, gb)
        g = jax.tree_util.tree_map(lambda a: a / n_tok, g)
        losses.append(total / n_tok)
        if first is None:
            first = flat_norms(_leaf_norms(g))
        params, m, v = _adam(params, m, v, g, jnp.float32(t), float(lr),
                             float(b1), float(b2))
        del g
    del m, v
    return {"losses": losses, "first_grad_norms": first, "params": params,
            "init": make}


@jax.jit
def _norm_of_diff(a, b):
    d = a - b
    return jnp.sqrt(jnp.sum(d * d))


def change_norms_stacked(ref, result: dict) -> dict:
    """Leaf norms of (final - seeded) parameters of a ``run_steps``
    result, flat by layer."""
    init = result["init"]()
    diff = jax.tree_util.tree_map(jnp.subtract, result["params"], init)
    del init
    return flat_norms(_leaf_norms(diff))


def change_norms_flat(ref, cfg: dict, seed: int, flat_host: dict) -> dict:
    """The same for parameters some OTHER code trained (host arrays by
    flat per-layer name): each leaf against the seeded leaf of that
    name, regenerated here."""
    init = common.make_params(ref.param_specs(cfg), ref.n_layers(cfg),
                              cfg["initializer_range"], seed)
    return {k: float(_norm_of_diff(jnp.asarray(flat_host[k], jnp.float32),
                                   init[k])) for k in init}
