"""Mixture-of-Experts FFN with expert parallelism — the ``ep`` axis.

No reference counterpart (SURVEY §2.2: the reference's only axis is
data parallelism); this is the TPU rebuild's expert-parallel extension,
built the way the hardware wants it (GShard/Switch): top-1 routing
with a STATIC per-expert capacity (XLA needs static shapes — dropped
tokens pass through on the residual), dispatch/combine as one-hot
einsums that lower to MXU matmuls, and — under ``shard_map`` — one
``all_to_all`` each way over the axis that shards the tokens, so each
device keeps ``n_experts / n_shards`` experts' weights AND their
optimizer state.

Like the tensor-parallel layers, the module stores FULL ``[E, ...]``
expert weights on the host; sharding happens at trace time via param
specs (``parallel.spmd.param_specs`` shards the leading expert dim over
``axis_name``, router weights stay replicated).  ``axis_name=None`` (or
an unbound axis — eager use) runs the dense dispatch.

Capacity semantics differ between the two paths when capacity binds:
the dense path budgets ``C = ceil(f·N/E)`` slots per expert globally,
while the parallel path budgets ``C_local = ceil(f·N_local/E)`` per
(source shard, expert) pair — GShard's convention; a shard that routes
an unusually large fraction of ITS tokens to one expert drops some the
dense path would have kept.  With capacity loose enough that nothing
drops, the two paths compute exactly the same function (pinned in
tests/test_moe.py).

Routing: ``top_k=1`` (default) is Switch — one expert per token, raw
softmax gate.  ``top_k=2`` is GShard-style — the two gates renormalize
to sum 1 and capacity is granted in choice order (all first choices
claim slots before any second choice), so when capacity binds the
less-confident assignments drop first.  Both ride the same [E, C]
dispatch/combine einsums and the same all_to_all wire.

Load balancing: ``aux_loss_coef > 0`` enables the Switch auxiliary
loss ``E · Σ_e f_e · P_e`` (f_e = fraction of tokens FIRST-choice
routed to expert e pre-capacity, P_e = mean router probability).  The activation-
dependent term travels on the framework's buffer thread — the layer
writes it to an ``aux_loss`` buffer, which the train-step builders
read back INSIDE the differentiated loss function and add to the
criterion loss, so its gradient falls out of autodiff
(:func:`collect_aux_paths` / :func:`aux_loss_term`).  Optional router
``jitter`` adds Switch's multiplicative noise on top.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..nn.initialization import (IN_OUT, ONE_D, RandomNormal, Xavier,
                                 Zeros)
from ..nn.module import FLOAT32_LEAVES, TensorModule  # noqa: F401


class MoEFFN(TensorModule):
    """Switch-style top-1 MoE feed-forward over [batch, seq, embed].

    ``n_experts`` expert MLPs (``embed -> hidden -> embed``, gelu); a
    linear router picks each token's expert, scaled by its softmax
    gate — or, with ``top_k=2``, the token's two best experts mixed by
    renormalized gates (GShard-style; capacity granted in choice
    order).  ``capacity_factor`` sizes the static per-expert buffer:
    ``C = ceil(capacity_factor * n_tokens / n_experts)`` — tokens over
    capacity are dropped (contribute zero; the transformer block's
    residual carries them through).  ``jitter`` multiplies router
    logits by uniform noise in [1-jitter, 1+jitter] during training
    (Switch Transformer's load-balance nudge).

    ``axis_name`` names the mesh axis that shards BOTH the tokens and
    the experts (expert parallelism rides the data axis); inside
    ``shard_map`` the dispatch becomes an ``all_to_all`` to the expert
    owners and back.  Unbound/None degrades to the dense dispatch —
    the same function, computed locally.
    """

    def __init__(self, embed_dim: int, hidden_dim: int, n_experts: int,
                 capacity_factor: float = 1.25, jitter: float = 0.0,
                 axis_name: Optional[str] = None,
                 aux_loss_coef: float = 0.0,
                 stat_axes: tuple = (), top_k: int = 1):
        super().__init__()
        if n_experts < 1:
            raise ValueError(f"n_experts must be >= 1, got {n_experts}")
        if not 1 <= top_k <= n_experts:
            raise ValueError(
                f"top_k must be in [1, n_experts={n_experts}], got {top_k}")
        self.embed_dim = embed_dim
        self.hidden_dim = hidden_dim
        self.n_experts = n_experts
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        self.jitter = float(jitter)
        self.axis_name = axis_name
        self.aux_loss_coef = float(aux_loss_coef)
        # extra mesh axes the tokens are sharded over beyond axis_name
        # (e.g. a 'seq' axis): routing statistics for the aux loss are
        # pmean'd over them too, so the term stays the GLOBAL formula
        if isinstance(stat_axes, str):  # tuple("seq") == ('s','e','q')
            stat_axes = (stat_axes,)
        self.stat_axes = tuple(stat_axes)
        self.reset()

    def reset(self):
        w_init = self._init_methods.get("weight", (Xavier(), None))[0]
        b_init = self._init_methods.get("bias", (Zeros(), None))[0]
        E, D, H = self.n_experts, self.embed_dim, self.hidden_dim
        self._register_param("router_w", w_init.init((E, D), IN_OUT))
        self._register_param("router_b", b_init.init((E,), ONE_D))
        wi = np.stack([np.asarray(w_init.init((H, D), IN_OUT)).T
                       for _ in range(E)])
        wo = np.stack([np.asarray(w_init.init((D, H), IN_OUT)).T
                       for _ in range(E)])
        self._register_param("wi", jnp.asarray(wi))       # [E, D, H]
        self._register_param("bi", jnp.zeros((E, self.hidden_dim)))
        self._register_param("wo", jnp.asarray(wo))       # [E, H, D]
        self._register_param("bo", jnp.zeros((E, self.embed_dim)))
        if getattr(self, "aux_loss_coef", 0.0) > 0.0:
            # registered only when enabled so aux-free MoE stays
            # buffer-free (the pipeline path requires that)
            self._register_buffer("aux_loss", jnp.zeros((), jnp.float32))
        return self

    # -- helpers -------------------------------------------------------
    def _n_shards(self):
        """Bound-axis size, or 1 when eager/unbound (RowParallelLinear's
        detection pattern)."""
        if self.axis_name is None:
            return 1
        try:
            return lax.psum(1, self.axis_name)
        except NameError:
            return 1

    def _route(self, x2d, params, training, rng):
        """Top-k routing: (dispatch [N, E, C] binary, combine [N, E, C]
        gate-weighted, aux) — capacity-masked slot assignment.

        ``top_k == 1`` is Switch (raw softmax gate); ``top_k > 1`` is
        GShard-style: the k gates renormalize to sum 1, and capacity is
        granted in choice order — ALL first choices claim slots before
        any second choice, so when capacity binds the less-confident
        assignments drop first."""
        logits = jnp.dot(x2d, params["router_w"].T) + params["router_b"]
        if training and self.jitter > 0.0 and rng is not None:
            noise = jax.random.uniform(
                rng, logits.shape, logits.dtype,
                1.0 - self.jitter, 1.0 + self.jitter)
            logits = logits * noise
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gk, idxk = lax.top_k(probs, self.top_k)               # [N, K]
        if self.top_k > 1:
            gk = gk / jnp.sum(gk, axis=-1, keepdims=True)
        C = self._capacity(x2d.shape[0])
        disp = jnp.zeros((x2d.shape[0], self.n_experts, C), jnp.float32)
        comb = jnp.zeros_like(disp)
        counts = None
        for c in range(self.top_k):                          # K static
            oh = jax.nn.one_hot(idxk[:, c], self.n_experts,
                                dtype=jnp.float32)            # [N, E]
            pos, keep, counts = self.keep_mask(oh, counts)
            slot = (jax.nn.one_hot((pos - 1).astype(jnp.int32), C,
                                   dtype=jnp.float32)
                    * keep[..., None])                        # [N, E, C]
            disp = disp + slot
            comb = comb + gk[:, c, None, None] * slot
        # Switch aux loss (pre-capacity): E * sum_e f_e * P_e, where
        # f_e = fraction of tokens FIRST-choice-routed to e, P_e = mean
        # prob (the standard formula for top-k too).  Under expert
        # parallelism the statistics are pmean'd over the axis FIRST so
        # the term is the documented GLOBAL formula — mean-of-products
        # of shard-local stats would silently differ from the dense
        # twin (product of global means).
        f_vec = jnp.mean(jax.nn.one_hot(idxk[:, 0], self.n_experts,
                                        dtype=jnp.float32), axis=0)
        p_vec = jnp.mean(probs, axis=0)
        for ax in (self.axis_name,) + self.stat_axes:
            if ax is None:
                continue
            try:
                f_vec = lax.pmean(f_vec, ax)
                p_vec = lax.pmean(p_vec, ax)
            except NameError:  # axis not bound: eager/unsharded call
                pass
        aux = self.n_experts * jnp.sum(f_vec * p_vec)
        return disp.astype(x2d.dtype), comb.astype(x2d.dtype), aux

    def _capacity(self, n_tokens: int) -> int:
        return max(1, int(np.ceil(self.capacity_factor * n_tokens
                                  / self.n_experts)))

    def keep_mask(self, onehot, counts=None):
        """The dispatch's keep rule, shared with diagnostics
        (models/generate.py capacity_bind_report re-applies it at decode
        time): first-come slot assignment via 1-based position-in-expert
        cumsum over the flattened token order, capacity from the token
        count.  ``counts`` [E] offsets the stream for later routing
        choices (top-k: every choice-c assignment queues behind all
        choice-(c-1) ones).  ``onehot`` [N, E] → (pos [N, E] 1-based,
        keep [N, E], new_counts [E])."""
        pos = jnp.cumsum(onehot, axis=0) * onehot             # 1-based
        if counts is not None:
            pos = (pos + counts[None, :]) * onehot
        C = self._capacity(onehot.shape[0])
        new_counts = jnp.sum(onehot, axis=0) + (
            counts if counts is not None else 0.0)
        return pos, (pos <= C) & (onehot > 0), new_counts     # [N, E]

    def nodrop(self, params, x):
        """The capacity-FREE top-k advance a decoder runs (each token
        simply uses its chosen experts: at inference nothing should be
        dropped), through the dropless dispatch below: assignments
        sorted by expert, one grouped product per projection (each
        expert's weights read at most once a call, none gathered per
        token), mixed by the (top-1 raw / top-k renormalized) gates.
        [B, Tq, D] -> [B, Tq, D]."""
        B, Tq, D = x.shape
        K = getattr(self, "top_k", 1)
        x2 = x.reshape(B * Tq, D)
        gk, idxk = route_top_k(x2, params["router_w"], params["router_b"], K,
                               "softmax", renormalize=K > 1)
        if getattr(BIND_TLS, "capture", None) is not None:
            # the training dispatch's keep rule (capacity from THIS
            # batch's token count; choice-ordered stream like _route) —
            # the fraction is over all N·K routing assignments
            kept, counts = 0.0, None
            for c in range(K):
                oh = jax.nn.one_hot(idxk[:, c], self.n_experts,
                                    dtype=jnp.float32)
                _, keep, counts = self.keep_mask(oh, counts)
                kept = kept + jnp.sum(keep.astype(jnp.float32))
            BIND_TLS.capture.append(1.0 - kept / (B * Tq * K))

        def gelu_experts(xs, sizes):
            e = row_experts(sizes, xs.shape[0])
            h = jax.nn.gelu(grouped_matmul(xs, params["wi"], sizes)
                            + params["bi"][e].astype(xs.dtype))
            return (grouped_matmul(h, params["wo"], sizes)
                    + params["bo"][e].astype(xs.dtype))

        y, _ = dropless_apply(x2, idxk, gk, (0, self.n_experts),
                              gelu_experts)
        return y.reshape(B, Tq, D)

    def _expert_mlp(self, inp, params):
        """inp [e, c, D] through the (possibly expert-sharded) stacked
        weights — the leading dims of ``inp`` and ``params['wi']``
        always agree (full E dense, E/n under shard_map)."""
        wi, bi = params["wi"], params["bi"]
        wo, bo = params["wo"], params["bo"]
        h = jnp.einsum("ecd,edh->ech", inp, wi.astype(inp.dtype))
        h = jax.nn.gelu(h + bi[:, None].astype(inp.dtype))
        out = jnp.einsum("ech,ehd->ecd", h, wo.astype(inp.dtype))
        return out + bo[:, None].astype(inp.dtype)

    # -- forward -------------------------------------------------------
    def _apply(self, params, buffers, x, training, rng):
        B, T, D = x.shape
        x2d = x.reshape(B * T, D)
        disp, comb, aux = self._route(x2d, params, training, rng)
        if self.aux_loss_coef > 0.0:
            buffers = dict(buffers)
            buffers["aux_loss"] = aux.astype(jnp.float32)
        n = self._n_shards()
        # expert_in[e, c] = the token dispatched to expert e slot c
        expert_in = jnp.einsum("nec,nd->ecd", disp, x2d)
        if n == 1:
            out_e = self._expert_mlp(expert_in, params)
        else:
            # to the expert owners: split the expert dim over the axis,
            # concat the shards' buffers along capacity -> each owner
            # sees [E/n, n*C, D]
            recv = lax.all_to_all(expert_in, self.axis_name,
                                  split_axis=0, concat_axis=1, tiled=True)
            out = self._expert_mlp(recv, params)
            # and back: split capacity, concat experts -> [E, C, D]
            out_e = lax.all_to_all(out, self.axis_name,
                                   split_axis=1, concat_axis=0, tiled=True)
        # the combine tensor carries the gates (top-1: the raw Switch
        # gate; top-k: the renormalized per-choice gates), so the
        # weighted mixture falls out of one einsum
        y = jnp.einsum("nec,ecd->nd", comb, out_e)
        return y.reshape(B, T, D), buffers


# --------------------------------------------------------------------------
# The dropless dispatch: top-k routing over ALL experts, the held
# experts' assignments sorted by expert, one grouped matrix product per
# projection, a weighted gather back.  Shared by ``DroplessMoE`` (gated
# experts, a share of the experts) and by decode's capacity-free advance
# of a ``MoEFFN`` (``MoEFFN.nodrop``).
# --------------------------------------------------------------------------

#: capacity-bind capture: while a list is installed on this thread
#: (``capture``), every ``MoEFFN.nodrop`` call appends the fraction of
#: its assignments the TRAINING dispatch's static capacity would have
#: dropped (trace-time side channel of ``capacity_bind_report``; absent
#: in a normal decode).  Thread-LOCAL, so a concurrent trace of another
#: model's generator cannot interleave its fractions into a report.
BIND_TLS = threading.local()

SCORINGS = ("softmax", "sigmoid")

#: rows of the sorted buffer one dispatch may hold: a longer token list
#: is routed at once and dispatched in equal pieces, so the
#: worst-case buffers of a long prefill stay a few hundred megabytes
MAX_DISPATCH_ROWS = 32768
#: up to this many pieces are a Python loop, more a ``lax.map``
MAX_UNROLLED_PIECES = 32


def route_top_k(x2, router_w, router_b, top_k: int,
                scoring: str = "softmax", renormalize: bool = True,
                select_bias=None, gate_scale: float = 1.0,
                renorm_eps: float = 1e-20):
    """Scores over ALL experts in float32 from ``x2`` [N, D] (whatever
    dtype it has: the product accumulates in float32), the ``top_k``
    largest, and their gates — the scores themselves, or divided by
    their sum under ``renormalize``.  ``select_bias`` [E] (the
    ``noaux_tc`` router's correction) is added to the scores for the
    SELECTION only: the gates are the unbiased scores of the chosen
    experts, renormalised with ``renorm_eps`` in the sum (``noaux_tc``'s
    ``1e-20``; ``lfm2_moe`` has ``1e-6``).
    ``gate_scale`` multiplies the gates last.  -> (gates [N, K] in at
    least float32, idx [N, K])."""
    with jax.named_scope("moe.route"):
        # at least float32 (a float64 oracle keeps its precision)
        ct = jnp.promote_types(x2.dtype, jnp.float32)
        logits = jnp.dot(x2, router_w.T.astype(x2.dtype),
                         preferred_element_type=ct)
        if router_b is not None:
            logits = logits + router_b.astype(ct)
        if scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        elif scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"scoring {scoring!r} not in {SCORINGS}")
        if select_bias is None:
            gates, idx = lax.top_k(scores, top_k)
            if renormalize:
                gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        else:
            _, idx = lax.top_k(scores + select_bias.astype(ct), top_k)
            gates = jnp.take_along_axis(scores, idx, axis=-1)
            if renormalize:
                gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                                 + renorm_eps)
        if gate_scale != 1.0:
            gates = gates * gate_scale
    return gates, idx


GROUPED_IMPLS = ("ragged", "gmm", "grouped_decode", "grouped_prefill")
# the plans :func:`grouped_matmul` chose while this thread traces an
# expert function, for the ``moe.schedule`` event of the dispatch
_TRACING = threading.local()


#: rows of the largest buffer a decode step dispatches: a buffer of
#: more rows is a piece of a prompt pass
DECODE_ROWS = 2048


def _tiles_of(impl: str, R: int, k: int, n: int):
    """``(rows, tk, tn)`` of one grid step of ``impl`` over a buffer of
    ``R`` rows at depth ``k`` and width ``n``; None for ``ragged`` (the
    compiler's own)."""
    if impl == "grouped_decode":        # the whole matrix
        from ..ops.grouped_decode import chunk_rows

        return (chunk_rows(R), k, n)
    if impl == "grouped_prefill":       # the whole matrix, a row tile
        from ..ops.grouped_prefill import ROW_TILE

        return (ROW_TILE, k, n)
    if impl == "gmm":       # PR 32's: 1024 where that divides, else 512
        return (128, 1024 if k % 1024 == 0 else 512,
                1024 if n % 1024 == 0 else 512)
    return None


def grouped_plan(R: int, k: int, n: int, dtype) -> tuple:
    """``(impl, tiles)`` of the grouped product ``[R, k] x [G, k, n]``
    held in ``dtype`` — the ONE rule :func:`grouped_matmul` and
    ``generate.cache_footprint`` read, from shapes and the backend
    alone (``PERF.md`` §6 "PR 32", "PR 43", "PR 47" and "PR 48" have the
    sweeps):

    * off a TPU: ``("ragged", None)``;
    * a buffer of more than ``DECODE_ROWS`` = 2048 rows (a piece of a
      prompt pass, bound by its operations):
      ``("grouped_prefill", (128, k, n))`` — the kernel of
      ``ops/grouped_prefill.py``, ONE k tile and the whole width, a
      group's matrix fetched once however many row tiles it spans —
      where the kernel itself takes the product
      (``grouped_prefill.fits``: whole row tiles, whole lane tiles, the
      matrix one tile within the kernel's 16 MiB of VMEM: SmallThinker's
      ``[2560, 768]`` asks for 9.9 MiB, LFM2's and GLM's
      ``[2048, 1536]`` for 15.8); else (Xing4.0's ``[3584, 1024]`` asks
      for 19.8, Command A+'s matrix is 32 MB) ``("ragged", None)``;
    * else ``("grouped_decode", (rows a product, k, n))`` — the kernel
      of ``ops/grouped_decode.py``, an expert's whole matrix one tile —
      where the kernel itself takes the product
      (``grouped_decode.fits``): the buffer is whole products of 128
      rows, or of 64 where 128 does not divide it (SmallThinker's 32
      rows x 6 choices = 192), depth and width are whole lane tiles,
      the matrix is within ``grouped_decode.WHOLE_BYTES`` (8 MB:
      SmallThinker's 3.9, LFM2's 6, Xing4.0's 7) and the call within the
      kernel's VMEM;
    * else, where 128 rows, 512 deep and 512 wide divide the product,
      ``("gmm", (128, tk, tn))``, megablox under PR 32's tiles: an
      expert of 32 MB in 2 MB tiles reads 92 % of its bytes' time there,
      and no way of cutting it read more;
    * else (a buffer of 32, 48 or 96 rows; a width that is no whole
      lane tiles) ``("ragged", None)``."""
    if jax.default_backend() != "tpu":
        return "ragged", None
    itemsize = jnp.dtype(dtype).itemsize
    if R > DECODE_ROWS:
        from ..ops import grouped_prefill

        impl = ("grouped_prefill" if grouped_prefill.fits(R, k, n, itemsize)
                else "ragged")
        return impl, _tiles_of(impl, R, k, n)
    from ..ops.grouped_decode import chunk_rows, fits

    impl = ("grouped_decode" if fits(R, k, n, itemsize, chunk_rows(R))
            else "gmm" if R % 128 == 0 and k % 512 == 0 and n % 512 == 0
            else "ragged")
    return impl, _tiles_of(impl, R, k, n)


def grouped_matmul(xs, w, group_sizes, impl: Optional[str] = None):
    """``xs`` [R, k] rows sorted by group, ``w`` [G, k, n]: row ``r`` of
    group ``g`` is multiplied by ``w[g]``; rows past ``sum(group_sizes)``
    come out UNDEFINED (the caller masks them).  ``impl``:

    * ``"ragged"`` — ``jax.lax.ragged_dot``: plain XLA off the TPU
      (differentiable by autodiff), the compiler's own grouped kernel in
      tiles of 512 on it;
    * ``"grouped_decode"`` — the repo's own Pallas kernel
      (``ops/grouped_decode.py``) for a decode step's buffer: one grid
      step a group, its whole matrix one contiguous tile of 3.9-8 MB,
      so every hit expert's weights cross HBM ONCE however its handful
      of rows lie in the buffer, which it walks in the products of 128
      or 64 rows the plan's tiles name.  TPU only;
    * ``"gmm"`` — the Pallas grouped matmul that ships with jax
      (megablox), rows in tiles of 128: a group that straddles a row
      tile is visited twice, and with more than one k tile its weights
      are fetched twice.  What a decode buffer without a
      ``grouped_decode`` plan keeps.  TPU only;
    * ``"grouped_prefill"`` — the repo's own Pallas kernel
      (``ops/grouped_prefill.py``) for a piece of a prompt pass: that
      grid of (row tile, group) visits with ONE k tile, an expert's
      whole matrix the weight tile — fetched once a group, the float32
      sum complete at the one write — and its visit lists made without
      a loop on the device.  TPU only.

    None picks by :func:`grouped_plan`."""
    R, k = xs.shape
    n = w.shape[-1]
    if impl is None:
        impl, tiles = grouped_plan(R, k, n, xs.dtype)
    elif impl in GROUPED_IMPLS:
        tiles = _tiles_of(impl, R, k, n)
    else:
        raise ValueError(f"grouped matmul {impl!r} not in {GROUPED_IMPLS}")
    plans = getattr(_TRACING, "plans", None)
    if plans is not None:
        plans.append((impl, tiles, k // tiles[1] if tiles else 0))
    if impl == "ragged":
        return lax.ragged_dot(xs, w.astype(xs.dtype), group_sizes)
    if impl == "grouped_decode":
        from ..ops.grouped_decode import grouped_decode

        return grouped_decode(xs, w, group_sizes, chunk=tiles[0])
    if impl == "grouped_prefill":
        from ..ops.grouped_prefill import grouped_prefill

        return grouped_prefill(xs, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(xs, w.astype(xs.dtype), group_sizes,
               preferred_element_type=xs.dtype, tiling=tiles)


def held_key(idx, held):
    """``idx`` with each expert by its place among the ``held`` =
    (first, count) ones, and ``count`` for every expert that is not."""
    first, count = held
    local = idx - first
    return jnp.where((local >= 0) & (local < count), local, count)


def dispatch_plan(idx, gates, held, rows: int):
    """Where each assignment to a held expert goes in the sorted buffer.

    ``idx`` / ``gates`` [N, K] from :func:`route_top_k`, ``held`` =
    (first, count).  Returns ``tok`` [R] (the token of each buffer row),
    ``valid`` [R], ``pos`` [N, K] (the buffer row of each assignment;
    any row for one that is not held, its ``weight`` is 0), ``weight``
    [N, K] f32 and ``sizes`` [count] int32 (rows per held expert).
    ``R = N * min(K, count)``: top-k picks distinct experts, so no token
    holds more than that — the buffer fits the worst imbalance and
    nothing is ever dropped."""
    count = held[1]
    N, K = idx.shape
    key = held_key(idx, held)
    is_held = key < count
    key = key.reshape(-1)                                     # [N*K]
    order = jnp.argsort(key, stable=True)       # held first, by expert
    sizes = jnp.sum(jax.nn.one_hot(key, count + 1, dtype=jnp.int32),
                    axis=0)[:count]
    inv = jnp.zeros((N * K,), jnp.int32).at[order].set(
        jnp.arange(N * K, dtype=jnp.int32))
    pos = jnp.minimum(inv, rows - 1).reshape(N, K)
    order = order[:rows]
    tok = (order // K).astype(jnp.int32)
    valid = jnp.arange(rows) < jnp.sum(sizes)
    weight = jnp.where(is_held, gates, 0.0)
    return tok, valid, pos, weight, sizes


def dispatch_pieces(N: int, K: int, count: int) -> tuple:
    """``(pieces, tokens a piece)`` of a dispatch of ``N`` tokens with
    ``K`` choices each among ``count`` held experts: one piece while the
    sorted buffer (``N * min(K, count)`` rows) stays within
    ``MAX_DISPATCH_ROWS``, else the fewest equal pieces that do."""
    per_tok = min(K, count)
    if N * per_tok <= MAX_DISPATCH_ROWS or N == 1:
        return 1, N
    pieces = -(-N * per_tok // MAX_DISPATCH_ROWS)
    while N % pieces:
        pieces += 1
    return pieces, N // pieces


def dropless_apply(x2, idx, gates, held, expert_fn):
    """The held experts' part of the mixture for ``x2`` [N, D]:
    ``sum_k weight[n, k] * expert_{idx[n, k]}(x2[n])`` over the choices
    that name a held expert; what the others would add is left out.

    ``expert_fn(xs, sizes) -> ys`` applies the experts to the sorted
    rows (:func:`row_experts` gives each row's expert, for a per-expert
    bias).  Returns (y [N, D], sizes [count] int32).  Static
    shapes for the worst case, nothing dropped; a token list whose
    buffer would pass ``MAX_DISPATCH_ROWS`` goes in equal pieces."""
    N, K = idx.shape
    pieces, n = dispatch_pieces(N, K, held[1])
    if pieces == 1:
        return _dropless_piece(x2, idx, gates, held, expert_fn)
    if pieces <= MAX_UNROLLED_PIECES:
        # unrolled, so that a generate program's device trace holds one
        # ``while`` — its decode scan (as the chunked SSD scan does)
        outs = [_dropless_piece(x2[lo:lo + n], idx[lo:lo + n],
                                gates[lo:lo + n], held, expert_fn)
                for lo in range(0, N, n)]
        return (jnp.concatenate([y for y, _ in outs]),
                sum(sizes for _, sizes in outs))
    ys, sizes = lax.map(
        lambda a: _dropless_piece(a[0], a[1], a[2], held, expert_fn),
        (x2.reshape(pieces, n, -1), idx.reshape(pieces, n, K),
         gates.reshape(pieces, n, K)))
    return ys.reshape(N, -1), jnp.sum(sizes, axis=0)


def _record_schedule(tokens: int, rows: int, held: int, k: int, plans):
    """One ``moe.schedule`` event in the process tracer's ring per
    traced dispatch: its shapes are static, so they are recorded where
    they are made (as ``flash.schedule`` is).  ``plans`` are the
    ``(impl, tiles, k tiles)`` :func:`grouped_matmul` chose for the
    expert function's products; the event names the first's arm
    (``impl``), its ``tiles`` ``[rows, tk, tn]`` (none for ``ragged``:
    the compiler's own) and the most ``k_tiles`` any of them walks (1:
    every weight tile holds the whole depth)."""
    from ..telemetry.tracer import default_tracer

    impl, tiles, _ = plans[0] if plans else (None, None, 0)
    tr = default_tracer()
    tr.record("moe.schedule", "compile", tr.clock(), 0.0, tokens=tokens,
              rows=rows, held=held, k=k, impl=impl, tiles=list(tiles or ()),
              k_tiles=max((p[2] for p in plans), default=0))


def _dropless_piece(x2, idx, gates, held, expert_fn):
    N, K = idx.shape
    rows = N * min(K, held[1])
    with jax.named_scope("moe.dispatch"):
        tok, valid, pos, weight, sizes = dispatch_plan(idx, gates, held,
                                                       rows)
        xs = jnp.take(x2, tok, axis=0)                        # [R, D]
    with jax.named_scope("moe.expert_matmul"):
        _TRACING.plans = plans = []
        try:
            ys = expert_fn(xs, sizes)
        finally:
            _TRACING.plans = None
    _record_schedule(N, rows, held[1], K, plans)
    with jax.named_scope("moe.combine"):
        # rows past the last assignment are whatever the grouped
        # product left there: zeroed before they are gathered
        ys = jnp.where(valid[:, None], ys, 0)
        picked = jnp.take(ys, pos.reshape(-1), axis=0).reshape(N, K, -1)
        ct = jnp.promote_types(x2.dtype, jnp.float32)
        y = jnp.einsum("nk,nkd->nd", weight.astype(ct), picked.astype(ct))
    return y.astype(x2.dtype), sizes


def row_experts(sizes, rows: int):
    """The held expert of each of the sorted buffer's ``rows`` (the last
    one for the rows past the last assignment)."""
    return jnp.minimum(jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(rows),
                                        side="right"), sizes.shape[0] - 1)


#: the gate's non-linearity in a gated expert: SwiGLU's, or ReGLU's
GATE_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def swiglu_experts(w_gate, w_up, w_down, activation: str = "silu"):
    """``expert_fn`` of gated experts: ``down(act(gate(x)) * up(x))``
    with ``w_gate`` / ``w_up`` [E, D, F] and ``w_down`` [E, F, D] — three
    grouped products and the gate; ``activation`` one of
    :data:`GATE_ACTIVATIONS` (``"silu"``: SwiGLU)."""
    act = GATE_ACTIVATIONS[activation]

    def fn(xs, sizes):
        g = grouped_matmul(xs, w_gate, sizes)
        u = grouped_matmul(xs, w_up, sizes)
        return grouped_matmul(act(g) * u, w_down, sizes)

    return fn


class DroplessMoE(TensorModule):
    """A gated mixture-of-experts FFN over [batch, seq, embed] that
    drops nothing and can hold a SHARE of its experts.

    The router scores ALL ``n_experts`` (``scoring`` ``"softmax"`` or
    ``"sigmoid"``, in float32), keeps the ``top_k`` largest and — under
    ``renormalize`` — divides their scores by their sum.  ``held =
    (first, count)`` says which experts' weights live here (default:
    all): the layer computes the part of the mixture its own experts
    give, and what absent experts would add is left out — the layer
    expert parallelism needs, run without its exchange.  Experts are
    gated MLPs ``embed -> hidden -> embed`` without biases
    (``activation``: the gate's ``"silu"``, SwiGLU, or ``"relu"``), stored
    ``w_gate`` / ``w_up`` [count, embed, hidden] and ``w_down`` [count,
    hidden, embed], drawn ``normal(0, init_std)`` unless an init method
    is set.  ``n_shared`` shared experts of the same shape see
    every token; their MEAN is added (``shared_gate`` / ``shared_up``
    [n_shared, embed, hidden], ``shared_down`` [n_shared, hidden,
    embed]).  ``score_bias`` adds the leaf ``score_bias`` [n_experts],
    float32 whatever dtype the others are held in
    (:data:`FLOAT32_LEAVES`), zeros until given: it enters the selection
    only (:func:`route_top_k`'s ``select_bias``) and the chosen scores
    are renormalised with ``renorm_eps`` in the sum; ``routed_scale``
    multiplies the gates.

    Dispatch (:func:`dropless_apply`): assignments to held experts
    sorted by expert, one grouped matrix product per projection, a
    weighted gather back; static shapes for the worst imbalance.
    ``apply_fn`` is plain differentiable jax off the TPU.

    ``routed(params, x2)`` returns the per-expert assignment counts
    beside the result, for the decode scan's counters; its
    ``scores_from`` is what the ROUTER multiplies where that is another
    tensor than the experts' input (a router placed before the block's
    attention reads the block's input)."""

    def __init__(self, embed_dim: int, hidden_dim: int, n_experts: int,
                 top_k: int = 2, scoring: str = "softmax",
                 renormalize: bool = True, n_shared: int = 0,
                 held: Optional[tuple] = None, init_std: float = 0.02,
                 score_bias: bool = False, routed_scale: float = 1.0,
                 renorm_eps: float = 1e-20, activation: str = "silu"):
        super().__init__()
        if activation not in GATE_ACTIVATIONS:
            raise ValueError(f"activation {activation!r} not in "
                             f"{tuple(GATE_ACTIVATIONS)}")
        self.activation = activation
        self.renorm_eps = float(renorm_eps)
        self.init_std = float(init_std)
        self.score_bias = bool(score_bias)
        self.routed_scale = float(routed_scale)
        if scoring not in SCORINGS:
            raise ValueError(f"scoring {scoring!r} not in {SCORINGS}")
        if not 1 <= top_k <= n_experts:
            raise ValueError(
                f"top_k must be in [1, n_experts={n_experts}], got {top_k}")
        first, count = held if held is not None else (0, n_experts)
        if not (0 <= first and count >= 1 and first + count <= n_experts):
            raise ValueError(f"held={held} is no range of the "
                             f"{n_experts} experts")
        self.embed_dim, self.hidden_dim = embed_dim, hidden_dim
        self.n_experts, self.top_k = n_experts, int(top_k)
        self.scoring, self.renormalize = scoring, bool(renormalize)
        self.n_shared = int(n_shared)
        self.held = (int(first), int(count))
        self.reset()

    def reset(self):
        D, F, (_, count) = self.embed_dim, self.hidden_dim, self.held
        custom = self._init_methods.get("weight", (None, None))[0]

        def stack(n, rows, cols):
            if custom is None:      # one draw a leaf: no fan to respect
                return RandomNormal(0.0, self.init_std).init((n, rows, cols))
            return jnp.stack([jnp.asarray(custom.init((cols, rows),
                                                      IN_OUT)).T
                              for _ in range(n)])

        self._register_param("router_w", (
            custom or RandomNormal(0.0, self.init_std)).init(
                (self.n_experts, D), IN_OUT))
        self._register_param("w_gate", stack(count, D, F))
        self._register_param("w_up", stack(count, D, F))
        self._register_param("w_down", stack(count, F, D))
        if self.n_shared:
            self._register_param("shared_gate", stack(self.n_shared, D, F))
            self._register_param("shared_up", stack(self.n_shared, D, F))
            self._register_param("shared_down", stack(self.n_shared, F, D))
        if self.score_bias:
            self._register_param("score_bias",
                                 jnp.zeros((self.n_experts,), jnp.float32))
        return self

    def shared(self, params, x2):
        """The mean of the shared experts on ``x2`` [N, D]: one plain
        matmul a matrix (``w[s]`` is a contiguous slice of the stacked
        leaf), summed in at least float32."""
        with jax.named_scope("moe.shared"):
            dt = x2.dtype
            ct = jnp.promote_types(dt, jnp.float32)
            act = GATE_ACTIVATIONS[getattr(self, "activation", "silu")]
            y = 0.0
            for s in range(self.n_shared):
                g = jnp.dot(x2, params["shared_gate"][s].astype(dt))
                u = jnp.dot(x2, params["shared_up"][s].astype(dt))
                y = y + jnp.dot(act(g) * u,
                                params["shared_down"][s].astype(dt),
                                preferred_element_type=ct)
            return (y / self.n_shared).astype(dt)

    def routed(self, params, x2, batch: Optional[int] = None,
               scores_from=None):
        """(the held experts' part plus the shared mean [N, D], the
        assignments each held expert took: [count] int32, or [batch,
        count] — by leading row of the ``batch`` the tokens came in).
        ``scores_from`` [N, D]: what the router multiplies (None: ``x2``,
        the experts' own input)."""
        # a layer names ``renorm_eps`` only where it departs from the
        # default: every other layer's call is the one it always made
        eps = getattr(self, "renorm_eps", 1e-20)
        gates, idx = route_top_k(x2 if scores_from is None else scores_from,
                                 params["router_w"], None, self.top_k,
                                 self.scoring, self.renormalize,
                                 params.get("score_bias"),
                                 self.routed_scale,
                                 **({} if eps == 1e-20
                                    else {"renorm_eps": eps}))
        y, sizes = dropless_apply(
            x2, idx, gates, self.held,
            swiglu_experts(params["w_gate"], params["w_up"],
                           params["w_down"],
                           getattr(self, "activation", "silu")))
        if self.n_shared:
            y = y + self.shared(params, x2)
        if batch is not None:
            count = self.held[1]
            sizes = jnp.sum(jax.nn.one_hot(
                held_key(idx, self.held).reshape(batch, -1), count + 1,
                dtype=jnp.int32), axis=1)[:, :count]
        return y, sizes

    def decode_plan(self, batch: int, dtype) -> dict:
        """The arm and the tile plan of the grouped products in a decode
        step of ``batch`` rows (one token a row, its choices among the
        held experts): ``grouped`` (``"ragged"``, ``"grouped_decode"``
        or ``"gmm"``), ``grouped_tiles`` (``"<rows a product>x<tk>x<tn>"``
        of the gate and up products; empty for ``ragged``) and
        ``grouped_tiles_down`` — :func:`grouped_plan`, the rule
        ``grouped_matmul`` itself reads.  As text: these ride on
        ``serve.dispatch`` into a profiler session, whose event metadata
        is split at commas."""
        return self._plan_words("grouped", batch, dtype)

    def prefill_plan(self, tokens: int, dtype) -> dict:
        """The same three words for ONE PIECE of a prompt pass of
        ``tokens`` tokens (:func:`dispatch_pieces`, the arithmetic
        :func:`dropless_apply` cuts it by): ``grouped_prefill``,
        ``grouped_prefill_tiles``, ``grouped_prefill_tiles_down``."""
        n = dispatch_pieces(tokens, self.top_k, self.held[1])[1]
        return self._plan_words("grouped_prefill", n, dtype)

    def _plan_words(self, word: str, tokens: int, dtype) -> dict:
        rows = tokens * min(self.top_k, self.held[1])
        D, F = self.embed_dim, self.hidden_dim
        impl, up = grouped_plan(rows, D, F, dtype)
        down = grouped_plan(rows, F, D, dtype)[1]
        return {word: impl,
                word + "_tiles": "x".join(map(str, up or ())),
                word + "_tiles_down": "x".join(map(str, down or ()))}

    def _apply(self, params, buffers, x, training, rng):
        B, T, D = x.shape
        y, _ = self.routed(params, x.reshape(B * T, D))
        return y.reshape(B, T, D), buffers


def collect_aux_paths(module, prefix=()):
    """Yield (buffer_tree_path, coef) for every MoEFFN with
    ``aux_loss_coef > 0`` — the same path addressing as
    ``Container.buffer_tree`` (children keyed by str(index)).  The
    train-step builders read these leaves from the forward's returned
    buffers INSIDE the loss function, where they are differentiable
    intermediates of the params."""
    from ..nn.module import Container

    if isinstance(module, MoEFFN):
        if module.aux_loss_coef > 0.0:
            yield prefix + ("aux_loss",), module.aux_loss_coef
    elif isinstance(module, Container):
        for i, child in enumerate(module.modules):
            yield from collect_aux_paths(child, prefix + (str(i),))


def aux_loss_term(buffers, paths):
    """Sum ``coef * buffers[path]`` over collected aux paths."""
    total = 0.0
    for path, coef in paths:
        node = buffers
        for k in path:
            node = node[k]
        total = total + coef * node
    return total
