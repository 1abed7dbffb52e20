"""Unified sharding-plan engine: ONE partitioner for every mesh shape.

The reference framework hard-wired exactly one parallelism mode
(synchronous data-parallel SGD over a block-manager all-reduce) and this
reproduction inherited that shape four times over — Local + Distri
data/multi-axis/pipeline were separately wired optimizer paths, and
every subsystem since (elastic, integrity, telemetry, async overlap)
paid the 4x threading tax.  This module replaces the four with two
pieces:

* :class:`Plan` — ordered regex rules mapping param-tree path names to
  :class:`~jax.sharding.PartitionSpec`s (the ``match_partition_rules``
  pattern).  :func:`derive_plan` generates the default rule set from
  module introspection (``spmd.param_specs`` — Column/RowParallel
  weights shard over ``model``, MoE expert stacks over their token
  axis, pipeline block stacks over ``pipe``), and FSDP-style rules
  shard large otherwise-replicated parameters over the ``data`` axis
  with gather-on-use.  Parallax (arxiv 1808.02621) is the reason the
  plan is *per-variable*: the right partitioning/transport differs
  across one param tree, and each rule now also picks its gradient
  *transport* — ``transport="sparse"`` ships a table's gradient over
  the data axis as ``(row_indices, row_values)`` instead of the dense
  all-reduce (docs/distributed.md "Gradient transport").

* :func:`compile_step_with_plan` — the ONE compiled-step builder.  For
  ANY mesh — data-only, data x model [x seq], data x pipe [x model]
  composed on a single mesh — it returns a :class:`CompiledPlanStep`
  with a uniform contract: ``step(params, slots, buffers, lr, x, y,
  rng, w, total_w) -> (loss, params, slots, buffers, ok, gnorm)``.
  Axes COMPOSE instead of being mutually exclusive modes; the driver
  threads elastic hooks, watchdog, integrity fingerprints, telemetry
  spans, prefetch infeed and async checkpointing through exactly once.

Gradient-reduction convention (one rule for every axis, generalizing
spmd.py's model axis and pipeline.py's pipe axis):

* a leaf SHARDED over an axis divides out that axis' replicated-loss
  cotangent amplification (``/n_axis``); for the ``data`` axis the
  shards arrive already summed — an FSDP leaf's whole cotangent is
  upcast to the MASTER dtype and ``psum_scatter``ed by hand (the
  backward of ``_gather_on_use``; never the gather's AD transpose,
  which would sum in the compute dtype), expert stacks ride their
  all_to_all's transpose — so unmasked steps divide by ``n_data`` and
  masked steps (loss pre-normalized by the global real count) take
  the sum as-is;
* a leaf REPLICATED over an axis pmeans its copies (psum over ``data``
  on masked steps — the weighted local losses sum to the global mean).
"""
from __future__ import annotations

import functools
import logging
import re
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger("bigdl_tpu")

__all__ = ["Rule", "Plan", "TRANSPORTS", "SYNCS", "FSDP_MIN_BYTES",
           "derive_plan",
           "named_leaves", "match_partition_rules",
           "compile_step_with_plan", "CompiledPlanStep", "spec_table"]


# ---------------------------------------------------------------------------
# path-named tree traversal
# ---------------------------------------------------------------------------

def named_leaves(tree, sep: str = "/", is_leaf=None):
    """Yield ``(name, leaf)`` with dict keys / sequence indices / NamedTuple
    fields joined by ``sep`` — the names the regex rules match against."""
    out = []

    def rec(node, prefix):
        if (is_leaf is not None and is_leaf(node)) or isinstance(node, P):
            out.append((sep.join(prefix), node))
        elif isinstance(node, dict):
            for k in node:
                rec(node[k], prefix + (str(k),))
        elif isinstance(node, tuple) and hasattr(node, "_fields"):
            for k, v in zip(node._fields, node):
                rec(v, prefix + (str(k),))
        elif isinstance(node, (tuple, list)):
            for i, v in enumerate(node):
                rec(v, prefix + (str(i),))
        else:
            out.append((sep.join(prefix), node))

    rec(tree, ())
    return out


def _map_named(fn, tree, sep: str = "/"):
    """Structure-preserving map of ``fn(name, leaf)`` over ``tree``."""
    def rec(node, prefix):
        if isinstance(node, P):
            return fn(sep.join(prefix), node)
        if isinstance(node, dict):
            return {k: rec(node[k], prefix + (str(k),)) for k in node}
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            return type(node)(*(rec(v, prefix + (str(k),))
                                for k, v in zip(node._fields, node)))
        if isinstance(node, (tuple, list)):
            return type(node)(rec(v, prefix + (str(i),))
                              for i, v in enumerate(node))
        return fn(sep.join(prefix), node)

    return rec(tree, ())


def _slot_tree_like(slots, per_param, default):
    """Mirror :func:`spmd.slot_specs`' structural rule for ANY per-param
    annotation tree: slot subtrees structured like the param tree
    inherit ``per_param`` (momentum/Adam moments follow their params);
    everything else (step counters) gets ``default``."""
    ptreedef = jax.tree_util.tree_structure(per_param)

    def rec(s):
        if jax.tree_util.tree_structure(s) == ptreedef:
            return per_param
        if isinstance(s, dict):
            return {k: rec(v) for k, v in s.items()}
        if isinstance(s, tuple) and hasattr(s, "_fields"):
            return type(s)(*(rec(v) for v in s))
        if isinstance(s, (tuple, list)):
            return type(s)(rec(v) for v in s)
        return default

    return rec(slots)


# ---------------------------------------------------------------------------
# rules + plan
# ---------------------------------------------------------------------------

#: gradient-transport vocabulary a :class:`Rule` may carry.  "dense" =
#: the classic all-reduce/pmean wire; "sparse" = the leaf's gradient
#: travels the data axis as ``(unique_row_indices, row_values)``
#: (Parallax, arxiv 1808.02621 — embedding tables touched by a skewed
#: batch produce >99%-zero-row gradients, and shipping the dense tensor
#: wastes nearly all collective bytes).  Anything else is rejected
#: loudly at plan-construction time.
TRANSPORTS = ("dense", "sparse")

#: synchrony vocabulary a :class:`Rule` may carry (docs/distributed.md
#: "Synchrony").  ``"step"`` = the classic lockstep reduction on every
#: iteration (the default — compiles the exact pre-sync program);
#: ``"periodic(k)"`` = local SGD: the leaf's gradient never crosses the
#: data axis, each data replica keeps its own copy, and every k-th step
#: the copies (and their momentum-style optimizer slots) all-reduce-
#: average under a traced flag — the DeepSpark/SparkNet relaxation
#: (arxiv 1602.08191) that trains through stragglers and cuts the
#: per-step wire by k; ``"stale(s)"`` = bounded-staleness sparse
#: updates for sparse-transport leaves: the local replica updates with
#: its own gradient immediately while the peers' index+row exchange is
#: applied up to ``s`` steps late (Parallax, arxiv 1808.02621 — sparse
#: embedding tables tolerate staleness dense MLPs don't).  Anything
#: else is rejected loudly at plan-construction time.
SYNCS = ("step", "periodic(k)", "stale(s)")

#: the FSDP threshold rule's default: on a mesh whose data axis has more
#: than one device, a dense lockstep leaf of at least this many bytes
#: that the plan would replicate over ``data`` lives on its data shard —
#: master, optimizer slots, reduced gradient and update — and only its
#: compute-dtype copy is ever whole (``Plan._maybe_auto_fsdp``).  What
#: ``derive_plan`` / ``compile_step_with_plan`` / ``Optimizer.set_fsdp``
#: apply unless told ``None`` / 0 ("replicate").
FSDP_MIN_BYTES = 1 << 20

_SYNC_RE = re.compile(r"^(?:step|periodic\((\d+)\)|stale\((\d+)\))$")

#: (table name, mesh shape) pairs whose degrade-to-replica warning has
#: already fired — entry_for runs once per leaf per retrace, and a
#: non-dividing table would otherwise repeat the same warning every
#: shrink/regrow retrace.  Bounded: cleared wholesale at capacity (the
#: set of live (table, mesh) pairs is tiny; losing dedup state just
#: means one extra warning).
_WARNED_REPLICA_TABLES: set = set()


def _parse_sync(sync: str):
    """``"step" | "periodic(k)" | "stale(s)"`` -> ``(kind, n)``; raises
    on anything outside the :data:`SYNCS` vocabulary."""
    m = _SYNC_RE.match(str(sync))
    if m is None:
        raise ValueError(
            f"unknown synchrony {sync!r} — expected one of {SYNCS} "
            "(docs/distributed.md \"Synchrony\")")
    if m.group(1) is not None:
        k = int(m.group(1))
        if k < 1:
            raise ValueError(f"periodic({k}) needs a period >= 1")
        return ("periodic", k)
    if m.group(2) is not None:
        s = int(m.group(2))
        if s < 1:
            raise ValueError(f"stale({s}) needs a staleness bound >= 1")
        return ("stale", s)
    return ("step", 0)


class Rule(NamedTuple):
    """One ordered partition rule: the first ``re.search`` match wins.

    ``spec`` is the leaf's PartitionSpec.  ``fsdp=True`` marks the rule's
    leaves for data-axis parameter sharding with gather-on-use (the spec
    then carries the data axis on the sharded weight dim) — the layout
    the threshold rule gives every large leaf by default, and the same
    compiled path: compute-dtype gather, master-dtype reduce-scatter,
    update on the shard; ``reason``
    documents where the rule came from (introspection kind, "fsdp",
    "user", "default").  ``transport`` picks the gradient wire for the
    rule's leaves (see :data:`TRANSPORTS`): ``"sparse"`` ships
    ``(row_indices, row_values)`` over the data axis instead of the
    dense all-reduce — with an automatic density-threshold fallback to
    dense per leaf (docs/distributed.md "Gradient transport").
    ``sync`` picks the rule's synchrony (see :data:`SYNCS`):
    ``"periodic(k)"`` runs local SGD with k-step parameter averaging,
    ``"stale(s)"`` bounded-staleness sparse updates — both opt-in per
    rule, never a silent numerics change."""

    pattern: str
    spec: P
    fsdp: bool = False
    reason: str = ""
    transport: str = "dense"
    sync: str = "step"


class _Entry(NamedTuple):
    spec: P
    fsdp: bool
    rule: Optional[Rule]
    transport: str = "dense"
    sync: str = "step"


def _spec_axes(spec) -> Tuple[str, ...]:
    axes = []
    for part in spec:
        if part is None:
            continue
        for a in (part if isinstance(part, tuple) else (part,)):
            axes.append(a)
    return tuple(axes)


def match_partition_rules(rules: Sequence[Rule], tree, sep: str = "/"):
    """Pytree of PartitionSpecs for ``tree`` under the ordered rules
    (the SNIPPETS.md [3] pattern).  Scalar / single-element leaves are
    never partitioned; an unmatched name raises — append a catch-all
    ``Rule(".*", P())`` for permissive plans."""
    plan = Plan(rules)
    return jax.tree_util.tree_map(
        lambda e: e.spec, plan.entries(tree, sep=sep),
        is_leaf=lambda e: isinstance(e, _Entry))


class Plan:
    """Ordered regex partition rules over param-tree path names.

    The plan is mesh-shape-agnostic until it is bound: rules name axes
    (``data``/``seq``/``model``/``pipe``); :meth:`bind` resolves them
    against a concrete mesh (axes the mesh lacks degrade to replication
    — with a structured warning, so a misconfigured mesh is diagnosable
    — and FSDP rules learn the data-axis size for divisibility).

    An explicit ``Plan`` shards what its rules say and nothing else:
    ``fsdp_min_bytes`` is ``None`` here (replicate); the default
    :data:`FSDP_MIN_BYTES` is ``derive_plan``'s, the plan nobody wrote."""

    def __init__(self, rules: Sequence[Rule], *, mesh: Optional[Mesh] = None,
                 fsdp_min_bytes: Optional[int] = None,
                 data_axis: str = "data",
                 sparse_density: Optional[float] = None):
        self.rules = tuple(Rule(*r) for r in rules)
        for r in self.rules:
            if r.transport not in TRANSPORTS:
                raise ValueError(
                    f"rule {r.pattern!r} names unknown gradient "
                    f"transport {r.transport!r} — expected one of "
                    f"{TRANSPORTS}")
            if r.transport == "sparse" and r.fsdp:
                raise ValueError(
                    f"rule {r.pattern!r} combines transport='sparse' "
                    "with fsdp=True — an FSDP leaf's gradient is "
                    "reduce-scattered onto its data shard; sparse "
                    "transport applies to data-replicated tables only")
            kind, _ = _parse_sync(r.sync)  # rejects unknown values
            if kind != "step" and r.fsdp:
                raise ValueError(
                    f"rule {r.pattern!r} combines sync={r.sync!r} with "
                    "fsdp=True — an FSDP leaf has exactly one copy "
                    "sharded over the data axis, so there are no "
                    "replicas to run local SGD on; relaxed synchrony "
                    "applies to data-replicated leaves only")
            if kind == "stale" and r.transport != "sparse":
                raise ValueError(
                    f"rule {r.pattern!r} asks for sync={r.sync!r} on "
                    f"transport={r.transport!r} — stale(s) is the "
                    "bounded-staleness SPARSE update path (Parallax); "
                    "use sync='periodic(k)' for dense leaves")
        self.mesh = mesh
        self.fsdp_min_bytes = fsdp_min_bytes
        self.data_axis = data_axis
        # sparse-transport row budget as a fraction of the table's rows:
        # the compiled step ships exactly ``ceil(rows * density)``
        # (index, row) pairs per shard per step, falling back to the
        # dense wire — at trace time when that budget's bytes would not
        # beat the dense all-reduce, at run time (in-program, exact)
        # when a batch touches more rows than the budget
        if sparse_density is None:
            from ..utils.engine import get_property

            sparse_density = float(get_property(
                "bigdl.sparse.density", 1.0 / 16))
        if not 0.0 < float(sparse_density) <= 1.0:
            raise ValueError(
                f"sparse_density must be in (0, 1], got {sparse_density}")
        self.sparse_density = float(sparse_density)

    # -- binding ---------------------------------------------------------
    def bind(self, mesh: Mesh) -> "Plan":
        return Plan(self.rules, mesh=mesh,
                    fsdp_min_bytes=self.fsdp_min_bytes,
                    data_axis=self.data_axis,
                    sparse_density=self.sparse_density)

    def _mesh_size(self, axis: Optional[str]) -> int:
        if self.mesh is None or axis is None:
            return 1
        return int(self.mesh.shape.get(axis, 1))

    def _degrade(self, spec: P) -> P:
        """Drop axes the bound mesh lacks (size-1 axes stay — they are
        valid spec entries)."""
        if self.mesh is None:
            return spec
        names = set(self.mesh.axis_names)

        def part(p):
            if p is None:
                return None
            if isinstance(p, tuple):
                kept = tuple(a for a in p if a in names)
                return kept if kept else None
            return p if p in names else None

        out = tuple(part(p) for p in spec)
        dropped = set(_spec_axes(spec)) - set(_spec_axes(P(*out)))
        if dropped:
            log.warning(
                "sharding plan: axis %s not in mesh %s — the rule's "
                "leaves run replicated over the missing axis (check the "
                "mesh shape if this model was built for it)",
                sorted(dropped), tuple(self.mesh.axis_names))
        return P(*out)

    # -- matching --------------------------------------------------------
    def entry_for(self, name: str, leaf) -> _Entry:
        shape = tuple(getattr(leaf, "shape", ()) or ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return _Entry(P(), False, None)  # never partition scalars
        for rule in self.rules:
            if re.search(rule.pattern, name) is None:
                continue
            spec = self._degrade(rule.spec)
            if rule.transport == "sparse" and not self._fits(spec, shape):
                # a sharded table whose rows stop dividing (elastic
                # shrink re-derives the mesh at survivor counts) falls
                # back to a full replica — rows re-partition or
                # replicate, they are never dropped.  Warn once per
                # (table, mesh): entry_for reruns on every retrace
                key = (name,
                       tuple(sorted(self.mesh.shape.items()))
                       if self.mesh is not None else None)
                if key not in _WARNED_REPLICA_TABLES:
                    if len(_WARNED_REPLICA_TABLES) >= 1024:
                        _WARNED_REPLICA_TABLES.clear()
                    _WARNED_REPLICA_TABLES.add(key)
                    log.warning(
                        "sharding plan: %s (%s) does not divide over "
                        "spec %s — the table runs replicated (sparse "
                        "transport still applies to its gradient)",
                        name, shape, _spec_str(spec))
                spec = self._strip_unfit(spec, shape)
            fsdp = rule.fsdp and self.data_axis in _spec_axes(spec)
            if fsdp and not self._fits(spec, shape):
                spec = P(*(self._strip_data(p) for p in spec))
                fsdp = False
            sync = self._effective_sync(name, rule.sync, spec)
            if not fsdp and rule.transport != "sparse" and sync == "step":
                # sparse-transport leaves keep their replica: the whole
                # point is that their gradient wire is already cheap,
                # so the FSDP threshold rule must not claim them; the
                # same holds for relaxed-synchrony leaves — local SGD
                # needs a whole replica per data shard
                spec = self._maybe_auto_fsdp(spec, leaf)
                fsdp = self.data_axis in _spec_axes(spec) and \
                    spec != self._degrade(rule.spec)
                if fsdp:
                    return _Entry(spec, True, rule, "dense", "step")
            return _Entry(spec, fsdp, rule, rule.transport, sync)
        raise ValueError(
            f"no partition rule matched param {name!r} — append a "
            "catch-all Rule('.*', P()) for replicate-by-default plans")

    def _effective_sync(self, name: str, sync: str, spec: P) -> str:
        """A rule's sync resolved against the leaf's final spec: a leaf
        SHARDED over the data axis has exactly one copy of each element
        — there are no replicas to relax, so ``periodic``/``stale``
        degrade to ``"step"`` with a warning (row-sharded embedding
        tables: the lookup exchange is the row's only copy)."""
        kind, _ = _parse_sync(sync)
        if kind == "step":
            return "step"
        if self.data_axis in _spec_axes(spec):
            log.warning(
                "sharding plan: %s asks for sync=%r but is sharded "
                "over the data axis (%s) — each element has exactly "
                "one copy, so the leaf runs sync='step' (relaxed "
                "synchrony applies to data-replicated leaves)",
                name, sync, _spec_str(spec))
            return "step"
        return sync

    def _strip_unfit(self, spec: P, shape) -> P:
        """Drop every spec dim whose combined axis size does not divide
        the dim extent (the sparse-table shrink degradation)."""
        parts = []
        for dim, part in enumerate(spec):
            if part is None or dim >= len(shape):
                parts.append(part)
                continue
            n = 1
            for a in (part if isinstance(part, tuple) else (part,)):
                n *= self._mesh_size(a)
            parts.append(part if n <= 1 or shape[dim] % n == 0 else None)
        while parts and parts[-1] is None:  # P(None) == P() (cosmetic)
            parts.pop()
        return P(*parts)

    def _strip_data(self, part):
        if part == self.data_axis:
            return None
        if isinstance(part, tuple):
            kept = tuple(a for a in part if a != self.data_axis)
            return kept if kept else None
        return part

    def _fits(self, spec: P, shape) -> bool:
        """Every sharded dim extent divides its axes' total size."""
        if self.mesh is None:
            return True
        for dim, part in enumerate(spec):
            if part is None or dim >= len(shape):
                continue
            n = 1
            for a in (part if isinstance(part, tuple) else (part,)):
                n *= self._mesh_size(a)
            if n > 1 and shape[dim] % n != 0:
                return False
        return True

    def _maybe_auto_fsdp(self, spec: P, leaf) -> P:
        """FSDP threshold rule: a large leaf left replicated over the
        data axis gets a divisible free dim sharded over it
        (compute-dtype gather on use, master-dtype gradient
        reduce-scatter, update on the shard).  ``None`` / 0 bytes:
        replicate.  Which dim is read off the shapes: the MINOR one
        where its shard is whole 128-lane tiles — the TPU compiler then
        scatters and gathers it as it lies, where a 4096-row major dim
        is padded to 4224 rows and routed through permutes and copies
        (PERF.md §6 "PR 44") — else the largest that divides."""
        if not self.fsdp_min_bytes:
            return spec
        n_data = self._mesh_size(self.data_axis)
        if n_data <= 1 or self.data_axis in _spec_axes(spec):
            return spec
        shape = tuple(leaf.shape)
        nbytes = int(np.prod(shape)) * jnp.dtype(leaf.dtype).itemsize
        if nbytes < self.fsdp_min_bytes:
            return spec
        parts = list(spec) + [None] * (len(shape) - len(spec))
        free = [dim for dim, ext in enumerate(shape)
                if parts[dim] is None and ext % n_data == 0]
        if not free:
            return spec  # no divisible free dim — stays replicated
        minor = len(shape) - 1
        best = (minor if minor in free
                and shape[minor] % (128 * n_data) == 0
                else max(free, key=lambda dim: (shape[dim], -dim)))
        parts[best] = self.data_axis
        return P(*parts)

    def entries(self, tree, sep: str = "/"):
        return _map_named(lambda n, l: self.entry_for(n, l), tree, sep=sep)

    def param_specs(self, tree):
        return jax.tree_util.tree_map(
            lambda e: e.spec, self.entries(tree),
            is_leaf=lambda e: isinstance(e, _Entry))

    def fsdp_tree(self, tree):
        return jax.tree_util.tree_map(
            lambda e: e.fsdp, self.entries(tree),
            is_leaf=lambda e: isinstance(e, _Entry))

    def has_fsdp(self, tree) -> bool:
        return any(jax.tree_util.tree_leaves(self.fsdp_tree(tree)))

    def transport_tree(self, tree):
        """Per-leaf gradient-transport pytree (``"dense"``/``"sparse"``)."""
        return jax.tree_util.tree_map(
            lambda e: e.transport, self.entries(tree),
            is_leaf=lambda e: isinstance(e, _Entry))

    def has_sparse(self, tree) -> bool:
        return any(t == "sparse" for t in
                   jax.tree_util.tree_leaves(self.transport_tree(tree)))

    def sync_tree(self, tree):
        """Per-leaf effective synchrony pytree (``"step"`` /
        ``"periodic(k)"`` / ``"stale(s)"`` strings)."""
        return jax.tree_util.tree_map(
            lambda e: e.sync, self.entries(tree),
            is_leaf=lambda e: isinstance(e, _Entry))

    def has_relaxed(self, tree) -> bool:
        """True when any leaf's effective sync is not ``"step"``."""
        return any(s != "step" for s in
                   jax.tree_util.tree_leaves(self.sync_tree(tree)))

    def named_entries(self, tree):
        return named_leaves(self.entries(tree),
                            is_leaf=lambda x: isinstance(x, _Entry))

    def table(self, tree) -> dict:
        """``{path name: "spec | transport | sync [markers]"}`` — the
        golden-test / docs view; the transport and sync columns ride
        every row (``BIGDL_REGEN_PLAN_GOLDENS=1`` regenerates the
        fixtures)."""
        return {name: (_spec_str(e.spec) + " | " + e.transport
                       + " | " + e.sync
                       + (" [fsdp]" if e.fsdp else ""))
                for name, e in self.named_entries(tree)}

    # -- sparse-transport sizing ----------------------------------------
    def sparse_budget(self, leaf) -> int:
        """Static (index, row) slots one shard ships per step for a
        sparse-transport leaf: ``ceil(rows * sparse_density)``."""
        rows = int(tuple(leaf.shape)[0])
        return max(1, int(np.ceil(rows * self.sparse_density)))

    _INDEX_BYTES = 4  # int32 row ids on the wire

    def _row_bytes(self, leaf) -> float:
        shape = tuple(leaf.shape)
        width = int(np.prod(shape[1:])) if len(shape) > 1 else 1
        return float(width * jnp.dtype(leaf.dtype).itemsize)

    def sparse_wire_bytes(self, leaf) -> float:
        """Actual bytes the sparse exchange moves for one step: every
        shard all_gathers its K ``(int32 index, row)`` pairs to the
        n_d - 1 peers (ring all-gather: each rank receives the other
        ranks' slots once)."""
        n_d = self._mesh_size(self.data_axis)
        k = self.sparse_budget(leaf)
        return (n_d - 1) * k * (self._row_bytes(leaf) + self._INDEX_BYTES)

    def _dense_data_wire(self, leaf, local_bytes: float) -> float:
        """The dense comparator: all-reduce of the leaf's local slice
        over the data axis (reduce-scatter + all-gather ring)."""
        n_d = self._mesh_size(self.data_axis)
        if n_d <= 1:
            return 0.0
        return 2.0 * (n_d - 1) / n_d * local_bytes

    def sparse_engaged(self, leaf, entry: _Entry) -> bool:
        """Trace-time density-threshold fallback: the sparse wire is
        taken only when its budgeted bytes actually beat the dense
        all-reduce — a table whose batches touch most rows (or a tiny
        table) keeps the dense wire.  Only data-replicated leaves
        qualify: rows sharded over the data axis already move
        per-lookup index+value bytes via their exchange's AD
        transpose."""
        if entry.transport != "sparse" or entry.fsdp:
            return False
        if _parse_sync(entry.sync)[0] == "periodic":
            # local SGD: the leaf's gradient never crosses the data
            # axis between averaging rounds, so the per-step sparse
            # wire never runs (the averaging round is accounted as
            # amortized dense bytes in collective_bytes)
            return False
        if self.data_axis in _spec_axes(entry.spec):
            return False
        if self._mesh_size(self.data_axis) <= 1:
            return False
        shape = tuple(getattr(leaf, "shape", ()) or ())
        if len(shape) < 1:
            return False
        nbytes = float(int(np.prod(shape))
                       * jnp.dtype(leaf.dtype).itemsize)
        shard_n = 1
        for a in _spec_axes(entry.spec):
            shard_n *= self._mesh_size(a)
        local = nbytes / max(shard_n, 1)
        return self.sparse_wire_bytes(leaf) < self._dense_data_wire(
            leaf, local)

    # -- collective accounting -------------------------------------------
    def collective_bytes(self, tree, compute_dtype=None) -> float:
        """Estimated collective wire bytes ONE training step moves for
        this plan's parameter/gradient traffic (what the telemetry
        ``bigdl_perf_collective_bytes`` gauge publishes).  Per leaf:

        * FSDP leaf: ``(n_d-1)/n_d x`` (the gathered leaf at
          ``compute_dtype`` bytes — the gather-on-use — plus the same
          at master bytes — the gradient's reduce-scatter, summed in
          the master dtype), plus the grad all-reduce of the slice over
          any OTHER replicated axes;
        * non-FSDP dense leaf: ``2(R-1)/R x local slice bytes`` where
          ``R`` is the product of the mesh axes the leaf is replicated
          over (the gradient pmean's reduce-scatter + all-gather pair);
          expert-parallel and sharded-embedding leaves (sharded over
          ``data``) reduce over no axis — their all_to_all/exchange
          ACTIVATION traffic is a token/lookup function, not accounted
          here;
        * sparse-transport leaf (engaged — see :meth:`sparse_engaged`):
          the data-axis component is the ACTUAL index+value wire,
          ``(n_d - 1) x K x (row bytes + 4)`` with
          ``K = ceil(rows x sparse_density)`` — not the dense formula;
          any other replicated axes still all-reduce the dense rows;
        * ``sync="periodic(k)"`` leaf: the data-axis component is the
          AMORTIZED averaging wire — the k-step parameter-averaging
          all-reduce's ring bytes divided by k (relaxed synchrony is
          cheaper, never free); other replicated axes still pmean the
          gradient every step.  ``stale(s)`` sparse leaves are
          unchanged: their index+value exchange still runs every step
          (only its *application* is allowed to lag).

        On a pure-data mesh with a replicate-everything plan this is
        exactly the old hard-wired ``2(n-1)/n x param bytes`` ring
        estimate; on composed meshes and FSDP plans it is what the
        hard-wired formula lied about (CHANGES.md PR 6).
        ``compute_dtype``: the step's (None: the masters' own).
        """
        if self.mesh is None:
            return 0.0
        axes = [a for a in self.mesh.axis_names if self._mesh_size(a) > 1]
        total = 0.0
        leaves = dict(named_leaves(tree))
        for name, entry in self.named_entries(tree):
            leaf = leaves[name]
            shape = tuple(getattr(leaf, "shape", ()) or ())
            nbytes = float(int(np.prod(shape or (1,)))
                           * jnp.dtype(leaf.dtype).itemsize)
            sharded = set(_spec_axes(entry.spec))
            shard_n = 1
            for a in sharded:
                shard_n *= self._mesh_size(a)
            local = nbytes / max(shard_n, 1)
            if entry.fsdp:
                n_d = self._mesh_size(self.data_axis)
                whole = local * n_d  # what one device gathers
                cast = (compute_dtype is not None and jnp.issubdtype(
                    leaf.dtype, jnp.floating))
                gathered = (whole * jnp.dtype(compute_dtype).itemsize
                            / jnp.dtype(leaf.dtype).itemsize
                            if cast else whole)
                total += (n_d - 1) / n_d * (gathered + whole)
                r = 1
                for a in axes:
                    if a not in sharded and a != self.data_axis:
                        r *= self._mesh_size(a)
                if r > 1:
                    total += 2.0 * (r - 1) / r * local
            elif self.sparse_engaged(leaf, entry):
                # index+value wire over data; dense over the rest
                total += self.sparse_wire_bytes(leaf)
                r = 1
                for a in axes:
                    if a not in sharded and a != self.data_axis:
                        r *= self._mesh_size(a)
                if r > 1:
                    total += 2.0 * (r - 1) / r * local
            elif _parse_sync(entry.sync)[0] == "periodic" \
                    and self.data_axis in axes \
                    and self.data_axis not in sharded:
                # local SGD: the averaging round's ring bytes / k, plus
                # the every-step gradient pmean over any OTHER
                # replicated axes (model peers stay lockstep)
                k = _parse_sync(entry.sync)[1]
                total += self._dense_data_wire(leaf, local) / k
                r = 1
                for a in axes:
                    if a not in sharded and a != self.data_axis:
                        r *= self._mesh_size(a)
                if r > 1:
                    total += 2.0 * (r - 1) / r * local
            else:
                r = 1
                for a in axes:
                    if a not in sharded:
                        r *= self._mesh_size(a)
                if r > 1:
                    total += 2.0 * (r - 1) / r * local
        return total

    def sparse_bytes_saved(self, tree) -> float:
        """Wire bytes one step does NOT move because sparse transport
        replaced the dense all-reduce (the
        ``bigdl_perf_sparse_bytes_saved`` gauge): per engaged leaf,
        dense data-axis ring bytes minus the budgeted index+value
        bytes."""
        if self.mesh is None:
            return 0.0
        saved = 0.0
        leaves = dict(named_leaves(tree))
        for name, entry in self.named_entries(tree):
            leaf = leaves[name]
            if not self.sparse_engaged(leaf, entry):
                continue
            shape = tuple(leaf.shape)
            nbytes = float(int(np.prod(shape))
                           * jnp.dtype(leaf.dtype).itemsize)
            shard_n = 1
            for a in _spec_axes(entry.spec):
                shard_n *= self._mesh_size(a)
            local = nbytes / max(shard_n, 1)
            saved += self._dense_data_wire(leaf, local) \
                - self.sparse_wire_bytes(leaf)
        return saved

    def sync_bytes_saved(self, tree) -> float:
        """Wire bytes one step does NOT move because relaxed synchrony
        replaced the lockstep data-axis reduction (the
        ``bigdl_perf_sync_bytes_saved`` gauge): per ``periodic(k)``
        leaf, the lockstep data-axis wire it would have paid every
        step (the sparse index+value wire when the leaf would have
        engaged sparse transport under ``sync="step"``, the dense ring
        otherwise) minus the amortized averaging bytes (ring / k).
        ``stale(s)`` leaves save nothing here — their exchange still
        runs every step."""
        if self.mesh is None:
            return 0.0
        saved = 0.0
        leaves = dict(named_leaves(tree))
        for name, entry in self.named_entries(tree):
            kind, k = _parse_sync(entry.sync)
            if kind != "periodic":
                continue
            if self.data_axis in _spec_axes(entry.spec) or entry.fsdp:
                continue
            n_d = self._mesh_size(self.data_axis)
            if n_d <= 1:
                continue
            leaf = leaves[name]
            shape = tuple(getattr(leaf, "shape", ()) or ())
            nbytes = float(int(np.prod(shape or (1,)))
                           * jnp.dtype(leaf.dtype).itemsize)
            shard_n = 1
            for a in _spec_axes(entry.spec):
                shard_n *= self._mesh_size(a)
            local = nbytes / max(shard_n, 1)
            dense = self._dense_data_wire(leaf, local)
            # what the leaf would have paid under sync="step"
            step_entry = entry._replace(sync="step")
            step_wire = (self.sparse_wire_bytes(leaf)
                         if self.sparse_engaged(leaf, step_entry)
                         else dense)
            saved += max(0.0, step_wire - dense / k)
        return saved


def _spec_str(spec: P) -> str:
    if not tuple(spec):
        return "replicated"
    def part(p):
        if p is None:
            return "-"
        if isinstance(p, tuple):
            return "(" + ",".join(p) + ")"
        return str(p)
    return "(" + ", ".join(part(p) for p in spec) + ")"


def spec_table(specs) -> dict:
    """``{path name: spec string}`` for a plain spec pytree."""
    return {name: _spec_str(s)
            for name, s in named_leaves(
                jax.tree_util.tree_map(
                    lambda s: s, specs,
                    is_leaf=lambda s: isinstance(s, P)))}


# ---------------------------------------------------------------------------
# default rule derivation (param_specs-style module introspection)
# ---------------------------------------------------------------------------

def _sparse_param_info(module, prefix=()):
    """'/'-joined param-tree names whose owning module opted into
    sparse gradient transport (``sparse_grads = True`` — e.g.
    ``nn.ShardedEmbedding``: a Zipf-skewed batch touches a vanishing
    fraction of its rows, Parallax's motivating case), mapped to the
    module's own ``sync_staleness`` override (None = follow the
    ``bigdl.sync.*`` knobs)."""
    from ..nn.module import Container

    out = {}
    if getattr(module, "sparse_grads", False):
        stale = getattr(module, "sync_staleness", None)
        for name, _ in named_leaves(module.param_tree()):
            out["/".join(prefix + (name,)) if name
                else "/".join(prefix)] = stale
    elif isinstance(module, Container):
        for i, child in enumerate(module.modules):
            out.update(_sparse_param_info(child, prefix + (str(i),)))
    return out


def _sparse_param_names(module, prefix=()):
    return set(_sparse_param_info(module, prefix))


def derive_plan(model, mesh: Mesh, *, model_axis: Optional[str] = "model",
                pipe_axis: Optional[str] = None,
                n_pipe: Optional[int] = None,
                fsdp_min_bytes: Optional[int] = FSDP_MIN_BYTES,
                sparse_density: Optional[float] = None,
                sync_period: Optional[int] = None,
                sync_staleness: Optional[int] = None,
                extra_rules: Sequence[Rule] = ()) -> Plan:
    """The default :class:`Plan` for ``model`` on ``mesh``.

    Module introspection (``spmd.param_specs`` — the partitioner the
    four hand-wired paths each re-derived) generates one exact-path
    rule per non-replicated parameter plus a replicate catch-all; a
    ``pipe_axis`` prepends the packed block stack's rules (leading
    layer dim over ``pipe``, composed with per-block tensor-parallel
    specs).  ``extra_rules`` go FIRST — user regex rules override the
    derived defaults.  ``fsdp_min_bytes`` is the threshold of the FSDP
    rule (see :meth:`Plan._maybe_auto_fsdp`; :data:`FSDP_MIN_BYTES` by
    default, ``None`` / 0 replicates; the pipeline layout never takes
    it).  Modules with
    ``sparse_grads = True`` get their rules stamped
    ``transport="sparse"`` (docs/distributed.md "Gradient
    transport").

    ``sync_period`` / ``sync_staleness`` (the ``bigdl.sync.period`` /
    ``bigdl.sync.staleness`` properties, ``Optimizer.set_sync_period``
    / ``set_sync_staleness``) set the default SYNCHRONY for the
    sparse-grads module rules — Parallax's hybrid, as two rule lines:
    dense MLP rules stay ``sync="step"``; a replicated sparse table's
    rule defaults to ``stale(s)`` when a staleness bound is armed
    (module-level ``staleness=`` overrides the global knob), else
    ``periodic(k)`` when an averaging period is armed; row-sharded
    table rules stay ``"step"`` (the lookup exchange is the row's only
    copy).  Dense rules opt in per rule via ``extra_rules``
    (docs/distributed.md "Synchrony")."""
    from .spmd import param_specs as module_specs

    if sync_period is None:
        from ..utils.engine import get_property

        _sp = get_property("bigdl.sync.period")
        sync_period = int(_sp) if _sp else None
    if sync_staleness is None:
        from ..utils.engine import get_property

        _ss = get_property("bigdl.sync.staleness")
        sync_staleness = int(_ss) if _ss else None
    model_axis = (model_axis if model_axis is not None
                  and model_axis in mesh.axis_names else None)
    rules = list(extra_rules)
    sparse_info = _sparse_param_info(model)
    sparse_names = set(sparse_info)
    if pipe_axis is not None:
        if sparse_names:
            raise NotImplementedError(
                "sparse gradient transport does not compose with the "
                "pipeline layout — the packed block stack has no "
                "per-table wire to sparsify; train sparse-table models "
                "on a data [x model] mesh "
                f"(sparse params: {sorted(sparse_names)})")
        from .pipeline import pack_params, param_specs as packed_specs

        packed = pack_params(model, n_pipe, model_axis)
        spec_tree = packed_specs(
            packed, pipe_axis,
            block=model.modules[_block_first(model)],
            model_axis=model_axis)
    else:
        spec_tree = module_specs(model, model_axis)
    for name, spec in named_leaves(spec_tree):
        if not isinstance(spec, P):
            continue
        transport = "sparse" if name in sparse_names else "dense"
        sync = "step"
        if transport == "sparse" and not tuple(spec):
            # data-REPLICATED sparse table: the leaf class that
            # tolerates relaxed synchrony (Parallax) — stale-bounded
            # sparse updates when a staleness bound is armed, local
            # SGD with periodic averaging when only a period is;
            # row-sharded tables (tuple(spec) non-empty) stay "step"
            stale = sparse_info.get(name) or sync_staleness
            if stale:
                sync = f"stale({int(stale)})"
            elif sync_period:
                sync = f"periodic({int(sync_period)})"
        if tuple(spec) or transport == "sparse":
            rules.append(Rule("^" + re.escape(name) + "$", spec,
                              reason="introspection",
                              transport=transport, sync=sync))
    rules.append(Rule(".*", P(), reason="default"))
    if pipe_axis and fsdp_min_bytes:
        # the packed block stack is stage-sharded and never gathered on use
        log.info("sharding plan: the pipeline layout keeps the "
                 "replicated update — the FSDP threshold (%d bytes) "
                 "is not applied", fsdp_min_bytes)
        fsdp_min_bytes = None
    return Plan(rules, mesh=mesh, fsdp_min_bytes=fsdp_min_bytes,
                sparse_density=sparse_density)


def _block_first(model) -> int:
    from .pipeline import _check_layout

    first, _count = _check_layout(model)
    return first


# ---------------------------------------------------------------------------
# the one compiled-step builder
# ---------------------------------------------------------------------------

class CompiledPlanStep:
    """The uniform compiled-step handle every driver loop consumes.

    ``step(params, slots, buffers, lr, x, y, rng=None, w=None,
    total_w=None) -> (loss, params, slots, buffers, ok, gnorm)`` for
    ANY mesh; ``kind`` is ``"model"`` (params are the module tree) or
    ``"packed"`` (the pipeline's stacked-block layout).  ``stage(x, y,
    w=None) -> (call, x, y, w)`` makes the stateless half of that call
    ahead of time, for ``step(..., call=call)``.  ``init_state``
    device-places fresh trees per the plan, ``sync_to_model`` writes
    them back host-side, ``eval_forward`` builds the matching compiled
    validation forward."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    # populated by compile_step_with_plan:
    #   kind, mesh, plan, model, optim, param_specs, slot_specs,
    #   buffer_specs, input_spec, io_spec, pad_multiple, step, stage,
    #   jitted_for, collective_bytes, update_sharded_bytes,
    #   sparse_bytes_saved,
    #   sync_bytes_saved, transport_table, sync_table, relaxed,
    #   periodic_cadences, stale_cadences, n_flags, has_relaxed,
    #   has_fsdp, fsdp_flags, n_data, n_seq

    def init_state(self, sync_resume=None):
        """Fresh device-placed (params, slots, buffers) from the live
        model/optimizer — each leaf is COPIED first: ``device_put`` of
        an array that already sits where it is asked to go returns the
        same buffer, and a replicated put aliases the source as its
        first shard (``may_alias=False`` does not stop that), so
        without the copy the donating step eats the model's own arrays
        and a failed attempt leaves the model unusable (the retry loop
        re-enters here, with or without a checkpoint to restore).

        Relaxed-synchrony leaves (``sync="periodic(k)"/"stale(s)"``)
        are stacked with a leading ``[n_data]`` replica dim sharded
        over the data axis — per-replica divergence is explicit device
        state, never a "replicated" array whose shards secretly
        differ.  ``sync_resume`` (the trainState checkpoint's ``sync``
        leg) restores the exact per-replica stacks for bitwise resume;
        absent or shape-mismatched (an elastic shrink changed n_data),
        every replica seeds from the model's averaged params — the
        forced averaging round a membership change demands."""
        from ..telemetry.tracer import default_tracer

        with default_tracer().span("plan.init_state", "state_sync"):
            return self._init_state(sync_resume)

    def _init_state(self, sync_resume):
        from ..optim.optimizer import _resume_slots

        resume = sync_resume or {}
        host = self._host_params()
        put = lambda tree, specs: jax.tree_util.tree_map(
            lambda a, s: jax.device_put(
                jnp.array(a, copy=True), NamedSharding(self.mesh, s)),
            tree, specs)
        slots_host = _resume_slots(self.optim,
                                   self.optim.init_state(host))
        if self.relaxed:
            host = self._stack_tree(host, self.relaxed,
                                    resume.get("params"))
            slot_relaxed = self._slot_relaxed(slots_host)
            slots_host = self._stack_tree(slots_host, slot_relaxed,
                                          resume.get("slots"))
        params = put(host, self.param_specs)
        slots = put(slots_host, self.slot_specs)
        buffers = put(self.model.buffer_tree(), self.buffer_specs)
        return params, slots, buffers

    # -- relaxed-synchrony state plumbing (docs/distributed.md) ---------
    def _slot_relaxed(self, slots) -> dict:
        """``{slot path: (kind, cadence)}`` for slot leaves that follow
        a relaxed param (the :func:`_slot_tree_like` structural rule —
        momentum-style slots replicate per data shard with their
        params; counters stay shared)."""
        if not self.relaxed:
            return {}
        # string tags (tuples would read as pytree nodes and break the
        # structural match)
        per_param = _map_named(
            lambda nm, l: ("%s:%d" % self.relaxed[nm]
                           if nm in self.relaxed else ""),
            self._host_params())
        tagged = _slot_tree_like(slots, per_param, "")
        return {name: tag for name, tag in named_leaves(tagged) if tag}

    def _stack_tree(self, tree, relaxed_names, resume_by_name=None):
        """Host-side replica stacking: each relaxed leaf becomes
        ``[n_data, *shape]`` — the checkpointed stack when its shape
        still matches, a broadcast of the (averaged) host value
        otherwise."""
        resume_by_name = resume_by_name or {}

        def stack(name, leaf):
            if name not in relaxed_names:
                return leaf
            arr = np.asarray(leaf)
            want = (self.n_data,) + arr.shape
            saved = resume_by_name.get(name)
            if saved is not None and tuple(np.shape(saved)) == want:
                return np.asarray(saved)
            return np.broadcast_to(arr, want).copy()

        return _map_named(stack, tree)

    def _unstack_host(self, tree, relaxed_names):
        """Collapse host-side replica stacks: float leaves average (the
        local-SGD read-out), everything else takes replica 0."""
        def unstack(name, leaf):
            if name not in relaxed_names:
                return leaf
            arr = np.asarray(leaf)
            if np.issubdtype(arr.dtype, np.floating):
                return arr.mean(axis=0).astype(arr.dtype)
            return arr[0]

        return _map_named(unstack, tree)

    def init_sync_state(self, sync_resume=None):
        """Device-placed relaxed-synchrony side state: the stale
        leaves' pending peer-contribution buffers (zeros on a fresh
        start; the checkpointed values on a bitwise resume).  ``{}``
        when the plan has relaxed leaves but none stale; None when
        every leaf is lockstep."""
        if not self.has_relaxed:
            return None
        resume = (sync_resume or {}).get("pending") or {}
        pending = {}
        specs_by_name = dict(named_leaves(self.param_specs))
        params_by_name = dict(named_leaves(self._host_params()))
        for name in self.stale_cadences:
            shape = (self.n_data,) + tuple(
                np.shape(params_by_name[name]))
            saved = resume.get(name)
            arr = (np.asarray(saved)
                   if saved is not None
                   and tuple(np.shape(saved)) == shape
                   else np.zeros(shape, np.float32))
            pending[name] = jax.device_put(
                jnp.asarray(arr, jnp.float32),
                NamedSharding(self.mesh, specs_by_name[name]))
        return pending

    def sync_snapshot(self, params, slots, sync_state) -> Optional[dict]:
        """Host snapshot of every per-replica stack + pending buffer —
        the trainState checkpoint leg that makes resume bitwise across
        an averaging boundary (None when nothing is relaxed)."""
        if not self.relaxed:
            return None
        host_p = jax.device_get(params)
        host_s = jax.device_get(slots)
        out = {"params": {name: np.asarray(leaf)
                          for name, leaf in named_leaves(host_p)
                          if name in self.relaxed},
               "slots": {name: np.asarray(leaf)
                         for name, leaf in named_leaves(host_s)
                         if name in self._slot_relaxed(host_s)}}
        if sync_state:
            out["pending"] = {name: np.asarray(jax.device_get(leaf))
                              for name, leaf in sync_state.items()}
        return out

    def eval_params(self, params):
        """The validation view of the device params: relaxed stacks
        collapse to their replica mean (the local-SGD read-out), so
        the eval forwards see model-shaped leaves."""
        if not self.relaxed:
            return params
        if getattr(self, "_eval_view", None) is None:
            names = dict(self.relaxed)

            def view(p):
                return _map_named(
                    lambda nm, l: (jnp.mean(l, axis=0)
                                   if nm in names and jnp.issubdtype(
                                       l.dtype, jnp.floating)
                                   else (l[0] if nm in names else l)), p)

            self._eval_view = jax.jit(view)
        return self._eval_view(params)

    def _host_params(self):
        if self.kind == "packed":
            from .pipeline import pack_params

            return pack_params(self.model, self.n_pipe, self.model_axis)
        return self.model.param_tree()

    def sync_to_model(self, params, slots, buffers):
        """Write the device trees back into the module/optimizer
        (whole trees on the host: FSDP leaves are gathered on the mesh
        first, ``device_get`` reassembles model-sharded ones — the
        out_specs make every output a global array; relaxed-synchrony
        replica stacks collapse to their mean, the local-SGD final
        model)."""
        from ..telemetry.tracer import default_tracer

        with default_tracer().span("plan.sync_to_model", "state_sync"):
            self._sync_to_model(params, slots, buffers)

    def _sync_to_model(self, params, slots, buffers):
        if self.kind == "packed":
            from .pipeline import unpack_params

            unpack_params(jax.device_get(params), self.model)
            self.optim._slots = jax.device_get(slots)
            return
        host_p = self._whole_on_host(params, self.fsdp_flags)
        host_s = self._whole_on_host(
            slots, _slot_tree_like(slots, self.fsdp_flags, False))
        if self.relaxed:
            host_p = self._unstack_host(host_p, self.relaxed)
            host_s = self._unstack_host(host_s,
                                        self._slot_relaxed(host_s))
        self.model.set_param_tree(host_p)
        self.model.set_buffer_tree(jax.device_get(buffers))
        self.optim._slots = host_s

    #: bytes of gathered FSDP leaves one fetch may hold whole on a chip
    _GATHER_BATCH_BYTES = 1 << 30

    def _whole_on_host(self, tree, fsdp):
        """Host copies of a device tree.  An FSDP leaf (``fsdp`` true)
        is gathered over the mesh first (a jitted identity onto the
        replicated sharding: a collective on the chips' own links), then
        ONE copy is fetched, a bounded batch of leaves at a time so no
        chip ever holds the whole tree; ``device_get`` of the shards
        would assemble them on the host, every byte a second pass over
        fresh memory.  Every other leaf comes back as ``device_get``
        reassembles it."""
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        gather = [i for i, f in enumerate(
            jax.tree_util.tree_leaves(fsdp)) if f]
        if gather and getattr(self, "_gather_whole", None) is None:
            self._gather_whole = jax.jit(
                lambda a: a,
                out_shardings=NamedSharding(self.mesh, P()))
        while gather:
            batch, size = [], 0
            while gather and (not batch
                              or size < self._GATHER_BATCH_BYTES):
                batch.append(gather.pop())
                size += leaves[batch[-1]].nbytes
            for i, a in zip(batch, jax.device_get(
                    [self._gather_whole(leaves[i]) for i in batch])):
                leaves[i] = a
        return jax.tree_util.tree_unflatten(treedef,
                                            jax.device_get(leaves))

    def checkpoint_tree(self, params, slots, buffers):
        """(orbax tree, kind) for the sharded-checkpoint path."""
        from ..optim.optimizer import Optimizer

        if self.relaxed:
            raise NotImplementedError(
                "orbax checkpoints do not carry relaxed-synchrony "
                "replica stacks yet — checkpoint sync='periodic/stale' "
                "runs with the pickle format (its trainState leg "
                "captures the per-replica state for bitwise resume)")
        if self.kind == "packed":
            return Optimizer._orbax_tree(params, slots), "packed"
        return Optimizer._orbax_tree(params, slots, buffers), "model"

    def place_batch(self, tree):
        """device_put a host batch pytree at the step's input sharding
        (so dispatch never pays a surprise reshard).  A numpy leaf goes
        from the host straight to its shards, never whole through the
        default device; a leaf already placed so comes back as it is."""
        spec = self.io_spec(tree)
        return jax.tree_util.tree_map(
            lambda a, s: jax.device_put(
                a if isinstance(a, (np.ndarray, jax.Array))
                else jnp.asarray(a), NamedSharding(self.mesh, s)),
            tree, spec)

    def param_bytes_by_device(self, params) -> dict:
        """bytes of addressable param shards per device — the FSDP
        acceptance measurement (per-device bytes ~ total/N under an
        FSDP plan, ~ total under replication)."""
        by_dev = {}
        for a in jax.tree_util.tree_leaves(params):
            for sh in getattr(a, "addressable_shards", ()):
                key = str(sh.device)
                by_dev[key] = by_dev.get(key, 0) + int(sh.data.nbytes)
        return by_dev


def _warn_dropped_axes(model, mesh, seq_axis, model_axis):
    """The diagnosability satellite: a model BUILT for an axis the mesh
    lacks used to run silently un-parallelized."""
    try:
        from .spmd import bound_axes

        bound = bound_axes(model)
    except Exception:
        return
    missing = sorted(a for a in bound if a not in mesh.axis_names)
    if missing:
        log.warning(
            "sharding plan: model binds mesh axis/axes %s but the mesh "
            "only has %s — those layers will run replicated/degraded; "
            "pass a mesh with the axis or rebuild the model without it",
            missing, tuple(mesh.axis_names))


def _stage(jitted_for, x, y, w=None):
    """``CompiledPlanStep.stage``: the half of a step's call that needs
    no state — the inputs as the arrays the compiled call takes, and the
    call for their structure — so that ``step(..., call=...)`` converts
    and looks up nothing between a loss and the next enqueue."""
    x = jax.tree_util.tree_map(jnp.asarray, x)
    y = jax.tree_util.tree_map(jnp.asarray, y)
    if w is not None:
        w = jnp.asarray(w, jnp.float32)
    return jitted_for(x, y, w is not None), x, y, w


def compile_step_with_plan(model, criterion, optim, mesh: Mesh,
                           plan: Optional[Plan] = None, *,
                           input_seq_dim: Optional[int] = None,
                           compute_dtype=None, donate: bool = False,
                           guard: bool = True, with_gnorm: bool = True,
                           n_microbatch: Optional[int] = None,
                           remat: Optional[bool] = None,
                           fsdp_min_bytes: Optional[int] = FSDP_MIN_BYTES,
                           sparse_density: Optional[float] = None,
                           sync_period: Optional[int] = None,
                           sync_staleness: Optional[int] = None,
                           data_axis: str = "data", seq_axis: str = "seq",
                           model_axis: str = "model",
                           pipe_axis: str = "pipe") -> CompiledPlanStep:
    """Build THE compiled train step for ``model`` over ``mesh``.

    One code path for every mesh shape: a ``pipe`` axis (size > 1)
    selects the packed GPipe layout (the schedule from
    ``pipeline._make_local_forward`` — lax.scan over ticks, ppermute
    ring, derived backward), everything else the flat SPMD layout; in
    BOTH cases the per-leaf partitioning, gradient reduction, guard and
    grad-norm come from the same :class:`Plan` machinery, so data /
    seq / model axes and FSDP param sharding compose freely.  FSDP is
    the DEFAULT layout of a derived plan's large leaves wherever the
    data axis has more than one device (``fsdp_min_bytes``,
    :data:`FSDP_MIN_BYTES`; ``None`` / 0 replicates; an explicit
    ``plan`` carries its own threshold): such a leaf's master, slots,
    reduced gradient and update live on its data shard, its
    compute-dtype copy is gathered before the forward, and its
    cotangent is upcast to the master dtype and reduce-scattered.  The
    pipeline layout keeps the replicated update (its stack is
    stage-sharded and never gathered on use).

    ``guard`` adds the in-program NaN/Inf skip-select (``ok`` output);
    ``with_gnorm`` the cross-shard global gradient norm (the flight
    recorder's fingerprint).  Disabling both reproduces the legacy
    ``spmd.make_train_step`` / ``pipeline.make_pipeline_train_step``
    programs bit-for-bit — those entry points are now shims over this
    builder.
    """
    from .spmd import (_cast_fwd, _check_moe, _in_spec_fn, _io_spec_fn,
                       _resolve_axes, bound_axes, slot_specs)

    d_ax, s_ax, m_ax = _resolve_axes(mesh, data_axis, seq_axis, model_axis,
                                     bound=bound_axes(model))
    _warn_dropped_axes(model, mesh, seq_axis, model_axis)
    # a pipe axis of ANY size selects the packed GPipe layout (the
    # driver normalizes size-1 axes away before building, so a plain
    # 4-axis default mesh never lands here by accident)
    p_ax = (pipe_axis if pipe_axis is not None
            and pipe_axis in mesh.axis_names else None)
    if p_ax is not None and s_ax is not None and mesh.shape[s_ax] > 1:
        raise ValueError(
            "the pipeline layout composes with data and model axes; a "
            ">1 seq axis is not supported with pipe — use a data x pipe "
            "[x model] mesh, or a seq mesh without pipe.")

    n_data = mesh.shape[d_ax] if d_ax else 1
    n_seq = mesh.shape[s_ax] if s_ax else 1
    n_model = mesh.shape[m_ax] if m_ax else 1
    n_pipe = mesh.shape[p_ax] if p_ax else 1

    if p_ax is not None:
        return _compile_pipeline(model, criterion, optim, mesh, plan,
                                 d_ax, m_ax, p_ax, n_microbatch,
                                 compute_dtype, donate, guard, with_gnorm,
                                 remat)

    # ---------------- flat SPMD layout (data x seq x model) -------------
    # single-device fast path (the LocalOptimizer shape): an unbound
    # model on a 1-device mesh needs no cross-device axes at all —
    # resolve them away and compile a plain jit below instead of
    # tracing through shard_map.  Size-1 collectives are identities,
    # so this is numerically the same program, cheaper to build.
    single = (int(np.prod(mesh.devices.shape)) == 1
              and not bound_axes(model))
    if single:
        d_ax = s_ax = m_ax = None
    _check_moe(model, mesh, d_ax, s_ax)
    if plan is None:
        plan = derive_plan(model, mesh, model_axis=m_ax,
                           fsdp_min_bytes=fsdp_min_bytes,
                           sparse_density=sparse_density,
                           sync_period=sync_period,
                           sync_staleness=sync_staleness)
    else:
        plan = plan.bind(mesh)
    host_params = model.param_tree()
    pspecs = plan.param_specs(host_params)
    fsdp_flags = plan.fsdp_tree(host_params)
    if single:
        # FSDP over one device is a no-op; never gather
        fsdp_flags = jax.tree_util.tree_map(lambda _: False, fsdp_flags)
    has_fsdp = any(jax.tree_util.tree_leaves(fsdp_flags))
    buffers = model.buffer_tree()
    sslots = slot_specs(optim.init_state(host_params), pspecs)
    bspecs = jax.tree_util.tree_map(lambda _: P(), buffers)

    # -- per-leaf gradient transport (Parallax; docs/distributed.md) ----
    # k_tree: static (index, row) budget per leaf — 0 compiles the
    # dense wire; > 0 compiles the sparse index+value exchange with an
    # in-program exact fallback when a batch overflows the budget.
    # transport_table records every decision for diagnosability.
    n_data = mesh.shape[d_ax] if d_ax else 1
    transport_table = {}
    _entries_by_name = dict(plan.named_entries(host_params))

    # -- per-leaf synchrony (docs/distributed.md "Synchrony") -----------
    # relaxed leaves keep one whole replica PER DATA SHARD: the engine
    # stacks them with a leading [n_data] dim sharded over the data
    # axis, so per-replica divergence is explicit, honest device state
    # and checkpoints capture it exactly.  sync_table records every
    # decision for diagnosability (the transport_table pattern).
    sync_table = {}
    relaxed = {}
    for _name, _leaf in named_leaves(host_params):
        _e = _entries_by_name[_name]
        _kind, _cadence = _parse_sync(_e.sync)
        if _kind == "step":
            continue
        if d_ax is None or n_data <= 1:
            sync_table[_name] = ("step (single data shard — nothing "
                                 "to relax)")
            continue
        relaxed[_name] = (_kind, _cadence)
        sync_table[_name] = (
            f"periodic (params + momentum slots average every "
            f"{_cadence} steps)" if _kind == "periodic" else
            f"stale (sparse exchange every step; peers' rows applied "
            f"one step late, bound {_cadence})")
    has_relaxed = bool(relaxed)
    periodic_cadences = tuple(sorted(
        {c for k_, c in relaxed.values() if k_ == "periodic"}))
    stale_cadences = {n: c for n, (k_, c) in relaxed.items()
                      if k_ == "stale"}
    n_flags = max(1, len(periodic_cadences))
    if has_relaxed:
        # stacked replica specs: the leading [n_data] dim shards over
        # data; the leaf's own dims keep their (model/seq) spec parts
        pspecs = _map_named(
            lambda nm, s: P(d_ax, *tuple(s)) if nm in relaxed else s,
            pspecs)
        sslots = slot_specs(optim.init_state(host_params), pspecs)
        # per-cadence membership masks for the averaging lax.cond
        # (static bools at trace time; slot masks follow the params
        # through the slot_specs structural rule)
        _slots0 = optim.init_state(host_params)
        group_param_masks = {
            c: _map_named(
                lambda nm, l, _c=c: relaxed.get(nm) == ("periodic", _c),
                host_params)
            for c in periodic_cadences}
        group_slot_masks = {
            c: _slot_tree_like(_slots0, group_param_masks[c], False)
            for c in periodic_cadences}

    def _k_of(name, leaf):
        e = _entries_by_name[name]
        if e.transport != "sparse":
            return 0
        if relaxed.get(name, ("", 0))[0] == "periodic":
            transport_table[name] = (
                "local (periodic sync — the gradient never crosses "
                "the data axis between averaging rounds)")
            return 0
        if d_ax is None or n_data <= 1:
            transport_table[name] = "dense (single data shard)"
            return 0
        spec = e.spec
        if d_ax in _spec_axes(spec):
            transport_table[name] = (
                "sparse (rows sharded over the data axis — the lookup "
                "exchange's AD transpose already carries index+value "
                "rows)")
            return 0
        if not plan.sparse_engaged(leaf, e):
            transport_table[name] = (
                "dense (density-threshold fallback: budgeted sparse "
                "wire would not beat the dense all-reduce)")
            return 0
        k = plan.sparse_budget(leaf)
        transport_table[name] = f"sparse (row budget K={k})"
        return k

    k_tree = _map_named(_k_of, host_params)

    in_spec = _in_spec_fn(d_ax, s_ax, input_seq_dim)
    io_spec = _io_spec_fn(in_spec)
    batch_axes = tuple(a for a in (d_ax, s_ax) if a)
    all_axes = tuple(a for a in (d_ax, s_ax, m_ax) if a)

    def _spec_has(spec, axis):
        return axis is not None and axis in _spec_axes(spec)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
    def _gather_on_use(shard, dim, master_dtype):
        """An FSDP leaf's compute copy: the master SHARD cast to the
        compute dtype, then gathered along its data-axis dim — half the
        master's bytes on the wire, and the whole master never exists.
        Its backward is written out, not derived: the gather's AD
        transpose would reduce-scatter the cotangent in the COMPUTE
        dtype; the plan's one reduction rule sums over chips in the
        master dtype, as the replicated leaves' ``pmean`` does."""
        with jax.named_scope("step.cast_params"):
            if compute_dtype is not None and jnp.issubdtype(
                    master_dtype, jnp.floating):
                shard = shard.astype(compute_dtype)
            return lax.all_gather(shard, d_ax, axis=dim, tiled=True)

    def _gather_fwd(shard, dim, master_dtype):
        return _gather_on_use(shard, dim, master_dtype), None

    def _gather_bwd(dim, master_dtype, _, g):
        with jax.named_scope("step.grad_reduce"):
            return (lax.psum_scatter(g.astype(master_dtype), d_ax,
                                     scatter_dimension=dim, tiled=True),)

    _gather_on_use.defvjp(_gather_fwd, _gather_bwd)

    def _gather_fsdp(p, p_c):
        """gather-on-use: the FSDP leaves of the compute tree ``p_c``
        come from their master shards in ``p`` (ZeRO-3's wire pattern,
        the reference's ``AllReduceParameter`` — SURVEY.md P3)."""
        def g(shard, leaf, spec, f):
            if not f:
                return leaf
            dim = next(i for i, part in enumerate(spec)
                       if part is not None and d_ax in
                       ((part,) if not isinstance(part, tuple) else part))
            return _gather_on_use(shard, dim, shard.dtype)

        return jax.tree_util.tree_map(g, p, p_c, pspecs, fsdp_flags)

    def _sparse_allreduce(g, k, spec):
        """Sparse gradient transport over the data axis: ship each
        shard's K touched ``(int32 row index, row values)`` pairs and
        segment-sum them back into the dense layout — exactly
        ``lax.psum(g, data)`` when every shard's touched-row count fits
        the budget (untouched budget slots carry zero rows, which
        scatter-add as no-ops).  When ANY shard overflows, an
        in-program ``lax.cond`` (predicate pmax'd over every axis the
        leaf is replicated on, so all peers take the same branch) falls
        back to the dense all-reduce — the exact-numerics guarantee
        never depends on the batch's density."""
        flat = g.reshape(g.shape[0], -1)
        # NaN/Inf rows compare unequal to zero, so anomalous gradients
        # still travel and the NaN guard sees them
        touched = jnp.any(flat != 0, axis=1)
        n_loc = jnp.sum(touched.astype(jnp.int32))
        repl_axes = tuple(a for a in all_axes if not _spec_has(spec, a))
        overflow = lax.pmax((n_loc > k).astype(jnp.int32),
                            repl_axes) > 0

        def sparse_branch(gf):
            # top_k on the 0/1 touched scores selects every touched
            # row first; zero rows pad the fixed budget
            _, idx = lax.top_k(touched.astype(jnp.float32), k)
            vals = jnp.take(gf, idx, axis=0)
            all_idx = lax.all_gather(idx, d_ax, tiled=True)
            all_vals = lax.all_gather(vals, d_ax, axis=0, tiled=True)
            return jnp.zeros_like(gf).at[all_idx].add(all_vals)

        def dense_branch(gf):
            return lax.psum(gf, d_ax)

        out = lax.cond(overflow, dense_branch, sparse_branch, flat)
        return out.reshape(g.shape)

    # per-leaf sync kind for the reduction rule ("step" | "periodic" |
    # "stale" — static strings at trace time)
    sync_kind_tree = _map_named(
        lambda nm, l: relaxed.get(nm, ("step", 0))[0], host_params)
    k_by_name = dict(named_leaves(k_tree))

    def _make_reduce_grad(masked):
        """The one gradient-reduction rule (module docstring)."""
        def reduce_grad(g, spec, k, sync):
            if sync != "step":
                # relaxed synchrony: the data axis is NOT reduced here
                # — the replica trains on its own local-mean gradient
                # (local SGD); stale leaves add the peers' one-step-
                # late contribution in _stale_exchange below.  Other
                # axes (seq/model peers of the SAME replica) stay
                # lockstep.
                for ax, n in ((s_ax, n_seq), (m_ax, n_model)):
                    if ax is None:
                        continue
                    g = g / n if _spec_has(spec, ax) else lax.pmean(g,
                                                                    ax)
                return g
            if d_ax:
                if _spec_has(spec, d_ax):
                    # FSDP (the master-dtype reduce-scatter of
                    # _gather_on_use's backward), expert stacks and
                    # sharded embedding rows (all_to_all/exchange
                    # transposes) arrive pre-summed over data
                    if not masked:
                        g = g / n_data
                elif k:
                    # sparse transport: indices+values on the wire,
                    # psum semantics out (pmean = /n below)
                    g = _sparse_allreduce(g, k, spec)
                    if not masked:
                        g = g / n_data
                else:
                    g = (lax.psum(g, d_ax) if masked
                         else lax.pmean(g, d_ax))
            for ax, n in ((s_ax, n_seq), (m_ax, n_model)):
                if ax is None:
                    continue
                if _spec_has(spec, ax):
                    g = g / n
                else:
                    g = lax.pmean(g, ax)
            return g

        return reduce_grad

    def _unstack_params(p):
        """Each shard's [1, ...] relaxed slices -> model-shaped leaves
        for the forward (AD restores the stacked shape on the grads)."""
        if not has_relaxed:
            return p
        return _map_named(
            lambda nm, l: l[0] if nm in relaxed else l, p)

    def _stale_exchange(grads, pending, masked):
        """Bounded-staleness sparse updates (Parallax): each shard
        applies its OWN gradient immediately plus the peers' summed
        contribution from the PREVIOUS step (the exchange 'in flight'
        — staleness exactly one step, within any declared bound s).
        The exchange itself still runs every step on the sparse
        index+value wire (accounting unchanged), it just stops gating
        the update application."""
        new_pending = {}

        def per(name, g):
            if name not in stale_cadences:
                return g
            k = k_by_name.get(name, 0)
            gl = g[0]  # the shard's replica slice, model-shaped
            # sum over data: the sparse wire when the budget engages
            # (spec P() -> the overflow predicate pmax's over EVERY
            # axis, so all shards branch together), dense psum when
            # the density threshold fell back
            total = (_sparse_allreduce(gl, k, P()) if k
                     else lax.psum(gl, d_ax))
            peers = total - gl
            new_pending[name] = peers[jnp.newaxis]
            stale_g = gl + pending[name][0]
            if not masked:
                stale_g = stale_g / n_data
            return stale_g[jnp.newaxis]

        return _map_named(per, grads), new_pending

    def _make_group_avg(pmask, smask):
        """The averaging round for one periodic cadence group: pmean
        the group's replica stacks (params + floating slots) over the
        data axis; counters and every other leaf pass through."""
        def avg(operand):
            p, s = operand
            p2 = jax.tree_util.tree_map(
                lambda a, m: lax.pmean(a, d_ax) if m else a, p, pmask)
            s2 = jax.tree_util.tree_map(
                lambda a, m: (lax.pmean(a, d_ax)
                              if m and jnp.issubdtype(a.dtype,
                                                      jnp.floating)
                              else a), s, smask)
            return p2, s2

        return avg

    from ..optim.regularizer import (collect_regularizer_paths,
                                     regularizer_loss)
    from ..resilience.guards import tree_finite, where_tree
    from .moe import aux_loss_term, collect_aux_paths

    upcast_out = not getattr(criterion, "accepts_low_precision", False)
    reg_paths = list(collect_regularizer_paths(model))
    aux_paths = list(collect_aux_paths(model))
    scale_tree = model.gradient_scale_tree()
    needs_scale = any(s != 1.0
                      for s in jax.tree_util.tree_leaves(scale_tree))

    def _run_fwd(p, buf, x, training, rng):
        """cast -> FSDP gather -> forward (the gather moves
        compute-dtype bytes and names its own scopes: its backward is
        the master-dtype reduce-scatter of ``step.grad_reduce``; the
        other leaves' cast transposes to the upcast of their f32
        master grads)."""
        from ..optim.optimizer import _cast_floats, _restore_dtypes

        p_c, x_c = p, x
        with jax.named_scope("step.cast_params"):
            if compute_dtype is not None:
                p_c = _cast_floats(p, compute_dtype)
                x_c = _cast_floats(x, compute_dtype)
        if has_fsdp:
            p_c = _gather_fsdp(p, p_c)
        with jax.named_scope("step.forward"):
            out, nb = model.apply_fn(p_c, buf, x_c, training, rng)
            if compute_dtype is not None:
                nb = _restore_dtypes(nb, buf)
        if compute_dtype is not None and upcast_out:
            # the criterion's input precision: booked with the loss
            with jax.named_scope("step.loss"):
                out = _cast_floats(out, jnp.float32)
        return out, nb

    def _spec_for_path(path):
        node = pspecs
        for k in path:
            node = node[k]
        return node

    # LOGGED loss psums a sharded param's reg penalty over the axes that
    # shard it (each shard sees only its slice: the model axis, and the
    # data axis of an FSDP leaf); per-slice reg GRADS are exact and ride
    # a separate pass (spmd.py's rule)
    reg_by_axes = {}
    for pr in reg_paths:
        spec = _spec_for_path(pr[0])
        reg_by_axes.setdefault(
            tuple(a for a in (d_ax, m_ax) if _spec_has(spec, a)),
            []).append(pr)

    def _reg_term(p):
        term = 0.0
        for axes, paths in reg_by_axes.items():
            part = regularizer_loss(p, paths)
            term = term + (lax.psum(part, axes) if axes else part)
        return term

    def _gnorm(grads):
        """||global grad||: per-leaf sum-squares, psum'd over exactly
        the axes each leaf is sharded on (replicated copies agree)."""
        groups = {}
        for g, spec in zip(jax.tree_util.tree_leaves(grads),
                           jax.tree_util.tree_leaves(
                               pspecs,
                               is_leaf=lambda s: isinstance(s, P))):
            axes = tuple(a for a in all_axes if _spec_has(spec, a))
            ss = jnp.vdot(g, g).astype(jnp.float32)
            groups[axes] = groups.get(axes, 0.0) + ss
        total = jnp.float32(0.0)
        for axes, ss in groups.items():
            total = total + (lax.psum(ss, axes) if axes else ss)
        return jnp.sqrt(total)

    def _make_local_step(masked):
        reduce_grad = _make_reduce_grad(masked)

        def local_step(params, slots, buf, lr, rng, x, y, *extra):
            if has_relaxed:
                sync_flags, pending = extra[0], extra[1]
                mask_args = extra[2:]
            else:
                sync_flags, pending = None, None
                mask_args = extra
            if rng is not None and batch_axes:
                # decorrelate dropout across batch shards; model peers
                # keep the SAME key (slices of one logical model)
                for a in batch_axes:
                    rng = jax.random.fold_in(rng, lax.axis_index(a))

            def loss_fn(p):
                out, nb = _run_fwd(_unstack_params(p), buf, x, True,
                                   rng)
                with jax.named_scope("step.loss"):
                    aux = (aux_loss_term(nb, aux_paths) if aux_paths
                           else 0.0)
                    if masked:
                        # trailing partial batch: per-record loss
                        # weighted 1-real/0-pad over the GLOBAL real
                        # count — every record of an epoch trains
                        # exactly once at static shape (reference
                        # DataSet.scala:255-288)
                        w, total_w = mask_args
                        add_axis = lambda v: jax.tree_util.tree_map(
                            lambda a: a[None], v)
                        per = jax.vmap(
                            lambda o, t: criterion._loss(
                                add_axis(o), add_axis(t)))(out, y)
                        return (jnp.sum(per * w) / total_w
                                + aux / n_data), nb
                    return criterion._loss(out, y) + aux, nb

            # device scopes (``jax.named_scope``, metadata only;
            # ``telemetry.tracer.DEVICE_SCOPES``): the backward needs
            # none of its own — autodiff wraps the forward's path, so
            # its operations read ``transpose(jvp(step.forward))/...``
            (loss, nb), grads = jax.value_and_grad(loss_fn,
                                                   has_aux=True)(params)
            with jax.named_scope("step.grad_reduce"):
                grads = jax.tree_util.tree_map(reduce_grad, grads,
                                               pspecs, k_tree,
                                               sync_kind_tree)
                if stale_cadences:
                    grads, new_pending = _stale_exchange(grads, pending,
                                                         masked)
                else:
                    new_pending = pending
            # ONE name for every pass over the parameter tree after
            # the reduce (regulariser, scales, norm, optimizer, guard,
            # periodic averaging): on the chip the guard's select is
            # the root of every optimizer fusion, so a name each books
            # the whole stretch to the guard and 0 to the optimizer
            with jax.named_scope("step.update"):
                if reg_paths:
                    # per-shard reg grads are exact — added AFTER the
                    # cross-shard reduction, never scaled by it
                    reg_g = jax.grad(
                        lambda p: regularizer_loss(p, reg_paths))(params)
                    grads = jax.tree_util.tree_map(lambda g, r: g + r,
                                                   grads, reg_g)
                    reg = _reg_term(params)
                    loss = loss + (reg / n_data if masked else reg)
                if needs_scale:  # reference setScaleW/setScaleB semantics
                    grads = jax.tree_util.tree_map(lambda g, s: g * s,
                                                   grads, scale_tree)
                gn = _gnorm(grads) if with_gnorm else jnp.float32(0.0)
            with jax.named_scope("step.grad_reduce"):
                if masked:
                    if d_ax:
                        loss = lax.psum(loss, d_ax)
                    if s_ax:
                        loss = lax.pmean(loss, s_ax)
                    # padded rows would pollute batch statistics: keep
                    # the pre-step buffers for the trailing partial
                    # batch
                    nb = buf
                elif batch_axes:
                    loss = lax.pmean(loss, batch_axes)
                    # sync running stats (BatchNorm) across batch shards
                    nb = jax.tree_util.tree_map(
                        lambda b: (lax.pmean(b, batch_axes)
                                   if jnp.issubdtype(b.dtype,
                                                     jnp.floating)
                                   else b),
                        nb)
            with jax.named_scope("step.update"):
                new_params, new_slots = optim.step(grads, params, slots,
                                                   lr)
                if guard:
                    # NaN/Inf anywhere skips the whole update; pmin
                    # over every axis makes all shards agree, so
                    # sharded slices stay consistent.  Relaxed leaves'
                    # grads are LOCAL on skip steps, but the pmin makes
                    # the skip decision uniform — shards never diverge
                    # on the guard.
                    ok_local = jnp.logical_and(tree_finite(grads),
                                               jnp.isfinite(loss))
                    ok = (lax.pmin(ok_local.astype(jnp.int32),
                                   all_axes) > 0
                          if all_axes else ok_local)
                    new_params = where_tree(ok, new_params, params)
                    new_slots = where_tree(ok, new_slots, slots)
                    nb = where_tree(ok, nb, buf)
                    if stale_cadences:
                        new_pending = where_tree(ok, new_pending,
                                                 pending)
                else:
                    ok = jnp.bool_(True)
                # the periodic averaging round: one lax.cond per
                # cadence group on its traced flag — averaging a
                # skipped step's (reverted) replicas is harmless and
                # keeps the cadence, so the round runs on both guard
                # phases
                for _gi, _cadence in enumerate(periodic_cadences):
                    avg = _make_group_avg(group_param_masks[_cadence],
                                          group_slot_masks[_cadence])
                    new_params, new_slots = lax.cond(
                        sync_flags[_gi] > 0, avg, lambda o: o,
                        (new_params, new_slots))
            if has_relaxed:
                return (loss, new_params, new_slots, nb, ok, gn,
                        new_pending)
            return loss, new_params, new_slots, nb, ok, gn

        return local_step

    _jitted_cache = {}

    def _jitted_for(x, y, masked):
        """shard_map specs are static: one executable per input
        tree-structure/rank signature (x masked variant)."""
        key = (jax.tree_util.tree_structure((x, y)), tuple(
            getattr(a, "ndim", 0)
            for a in jax.tree_util.tree_leaves((x, y))), masked)
        if key not in _jitted_cache:
            if single:  # no axes: the local step IS the global step
                fn = _make_local_step(masked)
            else:
                in_specs = (pspecs, sslots, bspecs, P(), P(),
                            io_spec(x), io_spec(y))
                out_specs = (P(), pspecs, sslots, bspecs, P(), P())
                if has_relaxed:
                    # traced averaging flags (replicated) + the stale
                    # leaves' pending buffers (stacked like their
                    # params)
                    pend_specs = {nm: _pspec_by_name[nm]
                                  for nm in stale_cadences}
                    in_specs = in_specs + (P(), pend_specs)
                    out_specs = out_specs + (pend_specs,)
                if masked:
                    # weight vector shards over data only (pad rows
                    # are whole records); the real count replicates
                    in_specs = in_specs + (P(d_ax), P())
                fn = shard_map(
                    _make_local_step(masked), mesh=mesh,
                    in_specs=in_specs,
                    out_specs=out_specs,
                    check_vma=False)
            _jitted_cache[key] = jax.jit(
                fn, donate_argnums=(0, 1, 2) if donate else ())
        return _jitted_cache[key]

    _pspec_by_name = dict(named_leaves(pspecs))
    _shape_by_name = {nm: tuple(np.shape(leaf))
                      for nm, leaf in named_leaves(host_params)}

    stage = functools.partial(_stage, _jitted_for)

    def step(params, slots, buffers, lr, x, y, rng=None, w=None,
             total_w=None, sync_flags=None, sync_state=None, call=None):
        if call is None:
            call, x, y, w = stage(x, y, w)
        if rng is None:  # deterministic default (ad-hoc/test use)
            rng = jax.random.PRNGKey(0)
        # host scalars: the compiled call moves them itself
        args = (params, slots, buffers, np.float32(lr), rng, x, y)
        if has_relaxed:
            flags = (jnp.zeros((n_flags,), jnp.int32)
                     if sync_flags is None
                     else jnp.asarray(sync_flags, jnp.int32))
            pend = sync_state
            if pend is None:  # ad-hoc use: fresh zero pending buffers
                pend = {nm: jnp.zeros(
                    (n_data,) + tuple(_shape_by_name[nm]), jnp.float32)
                    for nm in stale_cadences}
            args = args + (flags, pend)
        if w is not None:
            args = args + (w, np.float32(total_w))
        return call(*args)

    return CompiledPlanStep(
        kind="model", mesh=mesh, plan=plan, model=model, optim=optim,
        param_specs=pspecs, slot_specs=sslots, buffer_specs=bspecs,
        input_spec=in_spec(2), io_spec=io_spec, step=step, stage=stage,
        jitted_for=_jitted_for, pad_multiple=n_data,
        collective_bytes=plan.collective_bytes(host_params,
                                               compute_dtype),
        # master bytes whose update runs on ONE data shard (the gauge
        # bigdl_plan_update_sharded_bytes; ÷ ..._param_bytes_total is
        # the engagement share) — the flags as compiled, so 0 on one
        # device whatever a rule armed
        update_sharded_bytes=float(sum(
            a.nbytes for a, f in zip(
                jax.tree_util.tree_leaves(host_params),
                jax.tree_util.tree_leaves(fsdp_flags)) if f)),
        sparse_bytes_saved=plan.sparse_bytes_saved(host_params),
        sync_bytes_saved=plan.sync_bytes_saved(host_params),
        transport_table=transport_table, sync_table=sync_table,
        relaxed=relaxed, periodic_cadences=periodic_cadences,
        stale_cadences=stale_cadences, n_flags=n_flags,
        has_relaxed=has_relaxed,
        has_fsdp=has_fsdp, fsdp_flags=fsdp_flags, n_data=n_data,
        n_seq=n_seq, n_model=n_model, n_pipe=1, model_axis=m_ax, seq_axis=s_ax,
        input_seq_dim=input_seq_dim)


# ---------------------------------------------------------------------------
# the pipeline layout of the same builder
# ---------------------------------------------------------------------------

def _compile_pipeline(model, criterion, optim, mesh, plan, d_ax, m_ax,
                      p_ax, n_microbatch, compute_dtype, donate, guard,
                      with_gnorm, remat):
    """data x pipe [x model] composition: the GPipe schedule from
    pipeline.py's shared local forward, partitioned/reduced by the SAME
    Plan machinery as the flat layout."""
    from ..optim.regularizer import collect_regularizer_paths
    from ..resilience.guards import tree_finite, where_tree
    from .pipeline import (_check_model, _make_local_forward, pack_params)
    from .spmd import slot_specs

    S = mesh.shape[p_ax]
    n_data = mesh.shape[d_ax] if d_ax else 1
    n_model = mesh.shape[m_ax] if m_ax else 1
    M = int(n_microbatch or S)
    first, count = _check_model(model, S, m_ax)
    if list(collect_regularizer_paths(model)):
        raise NotImplementedError(
            "regularizers are not supported on the pipeline layout yet")
    if any(s != 1.0 for s in
           jax.tree_util.tree_leaves(model.gradient_scale_tree())):
        raise NotImplementedError(
            "scaleW/scaleB are not supported on the pipeline layout yet")
    if remat is None:
        remat = bool(getattr(model, "remat", False))
    upcast_out = not getattr(criterion, "accepts_low_precision", False)
    local_fwd = _make_local_forward(model, first, count, S, M, p_ax,
                                    compute_dtype, remat)

    packed0 = pack_params(model, S, m_ax)
    if plan is None:
        # derive_plan itself rejects sparse-grad modules under a pipe
        # axis — the packed stack has no per-table wire to sparsify
        plan = derive_plan(model, mesh, model_axis=m_ax, pipe_axis=p_ax,
                           n_pipe=S)
    else:
        plan = plan.bind(mesh)
        if plan.has_sparse(packed0):
            raise NotImplementedError(
                "sparse gradient transport does not compose with the "
                "pipeline layout — a transport='sparse' rule matched "
                "the packed block stack; use a data [x model] mesh for "
                "sparse-table models")
    if plan.has_relaxed(packed0):
        raise NotImplementedError(
            "relaxed synchrony (sync='periodic(k)'/'stale(s)') does "
            "not compose with the pipeline layout — the packed block "
            "stack's stages hand activations forward every tick, so "
            "there is no per-replica copy to let drift; train relaxed-"
            "sync models on a data [x model] mesh")
    pspecs = plan.param_specs(packed0)
    sslots = slot_specs(optim.init_state(packed0), pspecs)
    all_axes = tuple(a for a in (d_ax, p_ax, m_ax) if a)

    def _has(spec, axis):
        return axis is not None and axis in _spec_axes(spec)

    def _gnorm(grads):
        groups = {}
        for g, spec in zip(jax.tree_util.tree_leaves(grads),
                           jax.tree_util.tree_leaves(
                               pspecs,
                               is_leaf=lambda s: isinstance(s, P))):
            axes = tuple(a for a in all_axes if _has(spec, a))
            ss = jnp.vdot(g, g).astype(jnp.float32)
            groups[axes] = groups.get(axes, 0.0) + ss
        total = jnp.float32(0.0)
        for axes, ss in groups.items():
            total = total + (lax.psum(ss, axes) if axes else ss)
        return jnp.sqrt(total)

    def _make_local_step(masked):
        def local_step(packed, slots, buf, lr, rng, x, y, *mask_args):
            if rng is not None and d_ax:
                # decorrelate dropout across batch shards; pipe/model
                # peers keep the same base key (the stage already folds
                # tick+stage)
                rng = jax.random.fold_in(rng, lax.axis_index(d_ax))

            def loss_fn(p_master):
                out = local_fwd(p_master, x, True, rng, upcast_out)
                if masked:
                    w, total_w = mask_args
                    add_axis = lambda v: jax.tree_util.tree_map(
                        lambda a: a[None], v)
                    per = jax.vmap(
                        lambda o, t: criterion._loss(add_axis(o),
                                                     add_axis(t)))(out, y)
                    return jnp.sum(per * w) / total_w
                return criterion._loss(out, y)

            loss, grads = jax.value_and_grad(loss_fn)(packed)

            def reduce_grad(g, spec):
                # same one rule as the flat layout: pipe joins seq/model
                # as a "sharded divides, replicated pmeans" axis
                if d_ax:
                    g = (lax.psum(g, d_ax) if masked
                         else lax.pmean(g, d_ax))
                for ax, n in ((p_ax, S), (m_ax, n_model)):
                    if ax is None:
                        continue
                    g = g / n if _has(spec, ax) else lax.pmean(g, ax)
                return g

            grads = jax.tree_util.tree_map(reduce_grad, grads, pspecs)
            gn = _gnorm(grads) if with_gnorm else jnp.float32(0.0)
            if d_ax:
                loss = (lax.psum(loss, d_ax) if masked
                        else lax.pmean(loss, d_ax))
            new_p, new_slots = optim.step(grads, packed, slots, lr)
            if guard:
                ok_local = jnp.logical_and(tree_finite(grads),
                                           jnp.isfinite(loss))
                ok = lax.pmin(ok_local.astype(jnp.int32), all_axes) > 0
                new_p = where_tree(ok, new_p, packed)
                new_slots = where_tree(ok, new_slots, slots)
            else:
                ok = jnp.bool_(True)
            return loss, new_p, new_slots, buf, ok, gn

        return local_step

    in_batch = P(d_ax) if d_ax else P()
    bspecs = jax.tree_util.tree_map(lambda _: P(), model.buffer_tree())
    _jitted = {}

    def _jitted_for(x, y, masked):
        if masked not in _jitted:
            in_specs = (pspecs, sslots, bspecs, P(), P(), in_batch,
                        in_batch)
            if masked:
                in_specs = in_specs + (in_batch, P())
            sharded = shard_map(
                _make_local_step(masked), mesh=mesh, in_specs=in_specs,
                out_specs=(P(), pspecs, sslots, bspecs, P(), P()),
                check_vma=False)
            _jitted[masked] = jax.jit(
                sharded, donate_argnums=(0, 1, 2) if donate else ())
        return _jitted[masked]

    stage = functools.partial(_stage, _jitted_for)

    def step(packed, slots, buffers, lr, x, y, rng=None, w=None,
             total_w=None, call=None):
        if call is None:
            call, x, y, w = stage(x, y, w)
        args = (packed, slots, buffers, np.float32(lr),
                rng if rng is not None else jax.random.PRNGKey(0), x, y)
        if w is not None:
            args = args + (w, np.float32(total_w))
        return call(*args)

    in_spec_fn = lambda ndim: P(*((d_ax,) + (None,) * (ndim - 1))) \
        if d_ax else P()
    io_spec = lambda tree: jax.tree_util.tree_map(
        lambda a: in_spec_fn(getattr(a, "ndim", 0)), tree)

    return CompiledPlanStep(
        kind="packed", mesh=mesh, plan=plan, model=model, optim=optim,
        param_specs=pspecs, slot_specs=sslots, buffer_specs=bspecs,
        input_spec=in_batch, io_spec=io_spec, step=step, stage=stage,
        jitted_for=_jitted_for, pad_multiple=n_data * M,
        collective_bytes=plan.collective_bytes(packed0),
        update_sharded_bytes=0.0,
        sparse_bytes_saved=0.0, sync_bytes_saved=0.0,
        transport_table={}, sync_table={}, relaxed={},
        periodic_cadences=(), stale_cadences={}, n_flags=0,
        has_relaxed=False,
        has_fsdp=False, n_data=n_data, n_seq=1, n_model=n_model,
        n_pipe=S, n_microbatch=M, model_axis=m_ax, seq_axis=None,
        input_seq_dim=None)
