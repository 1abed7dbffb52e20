"""Paged KV-cache arena — pages instead of whole static buckets.

The unpaged decode path (`models/generate.py`) gives every in-flight
generate batch a dense ``[B, Hkv, T_cache, Dh]`` cache as long as its
prompt plus its ``max_new`` (rounded up to a multiple of 128, at most
``max_len``): a request that stops at an early eos after 40 tokens
still pins every position it MIGHT have filled for its whole lifetime
— ``T_max`` of them when callers ask for the whole window — so the
number of concurrent long decodes is bounded by the *worst-case*
window, not the *actual* one.  A :class:`KVPagePool` preallocates ONE
arena of fixed-size pages::

    arena_k / arena_v : [num_pages, layers, Hkv, page_size, Dh]

and each request holds a **page table** (a short list of page ids)
covering only the positions it has actually filled, extending one page
at a time as the decode grows.  At equal arena bytes the pool
therefore sustains ``T_max / T_actual`` times the concurrent requests
of the static-bucket path — the vLLM observation, at serving-control-
plane scale.

Allocation is host-side and O(1) (a free list under a lock); the
arena itself is a pair of device arrays updated *functionally* by the
paged decode programs (`models.generate.PagedDecoder`) — the pool
hands out page ids, the decoder gathers/scatters through them at
static shapes.  One writer at a time: the pool's ``arena_lock``
serializes read-modify-write of the arena reference (the serving
worker thread is the single writer in practice).

Exhaustion is an admission-control event, not an error: ``alloc``
raises :class:`PoolExhausted` and the server sheds the request with a
typed ``OVERLOADED`` — an un-servable decode must never be admitted.
Every lease is release-idempotent and the pool counts allocs/frees/
exhaustions plus a high-water mark, so leak detection is one
``free_pages == num_pages`` assert after drain.
"""
from __future__ import annotations

import threading
from typing import List, Optional

__all__ = ["KVPagePool", "PageLease", "PoolExhausted",
           "page_bucket_ladder", "page_bucket_for"]


class PoolExhausted(RuntimeError):
    """No free pages — the caller must shed (typed OVERLOADED), not
    block: a decode admitted without backing pages can never finish."""


def page_bucket_ladder(max_pages: int) -> List[int]:
    """Doubling page-table sizes ending exactly at ``max_pages`` —
    the compile ladder: one decode program per bucket, ever."""
    if max_pages < 1:
        raise ValueError("max_pages must be >= 1")
    ladder, b = [], 1
    while b < max_pages:
        ladder.append(b)
        b *= 2
    ladder.append(max_pages)
    return sorted(set(ladder))


def page_bucket_for(n: int, max_pages: int) -> int:
    """Smallest ladder bucket holding ``n`` pages."""
    for b in page_bucket_ladder(max_pages):
        if n <= b:
            return b
    raise PoolExhausted(
        f"page table of {n} exceeds max_pages {max_pages}")


class PageLease:
    """One request's hold on a set of pages.  ``extend`` grows it one
    allocation at a time as the decode crosses page boundaries;
    ``release`` is idempotent (the exhaustion/cancel/kill paths may
    race a finally-block release)."""

    __slots__ = ("pool", "pages", "owner", "_released")

    def __init__(self, pool: "KVPagePool", pages: List[int],
                 owner: Optional[str] = None):
        self.pool = pool
        self.pages = list(pages)
        self.owner = owner
        self._released = False

    def extend(self, n: int = 1) -> None:
        """Grow by ``n`` pages (raises :class:`PoolExhausted` — the
        already-held pages stay held; the caller decides whether to
        shed and release).  Growth is charged to the lease's owner, so
        a long decode keeps paying against its tenant's page budget."""
        if self._released:
            raise RuntimeError("lease already released")
        self.pages.extend(self.pool._take(n, self.owner))

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        self.pool._give(self.pages, self.owner)

    @property
    def released(self) -> bool:
        return self._released

    def __len__(self) -> int:
        return len(self.pages)


class KVPagePool:
    """Preallocated paged KV arena + free-list allocator.

    Parameters mirror the decode cache geometry: ``layers`` transformer
    blocks, ``num_kv_heads`` KV heads (GQA: may be fewer than query
    heads), ``page_size`` positions per page, ``head_dim`` features.
    ``dtype`` is the cache dtype (the paged path is full-precision
    only; the int8 cache stays a dense-path knob).

    The arena is built lazily on first use so constructing a pool (for
    sizing math, tests of the allocator) costs no device memory.
    """

    def __init__(self, num_pages: int, layers: int, num_kv_heads: int,
                 page_size: int, head_dim: int, dtype=None):
        if num_pages < 1:
            raise ValueError("num_pages must be >= 1")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.num_pages = int(num_pages)
        self.layers = int(layers)
        self.num_kv_heads = int(num_kv_heads)
        self.page_size = int(page_size)
        self.head_dim = int(head_dim)
        self.dtype = dtype
        self._lock = threading.Lock()
        #: serializes functional read-modify-write of the arena
        #: reference by decode programs (single-writer contract)
        self.arena_lock = threading.RLock()
        self._free = list(range(self.num_pages))
        self._arena_k = None
        self._arena_v = None
        # accounting (leak detection + the occupancy gauge family)
        self.allocs = 0
        self.frees = 0
        self.exhaustions = 0
        self.high_water = 0
        # owner-scoped accounting (multi-tenant fleets): pages held and
        # optional hard budgets per owner.  An owner over its budget is
        # refused (PoolExhausted → typed OVERLOADED shed) even while
        # the free list could cover it — one tenant's long decodes can
        # never exhaust the shared arena for everyone else.
        self._held = {}
        self._budgets = {}
        #: owner charged for allocations that don't name one — set to
        #: the serving model's name so decoder-internal allocs (the
        #: paged decode path allocates from inside models.generate)
        #: land on the right tenant without plumbing owner through the
        #: decoder
        self.default_owner: Optional[str] = None

    # ------------------------------------------------------------ sizing
    @classmethod
    def for_model(cls, model, num_pages: int, page_size: int = 16,
                  dtype=None) -> "KVPagePool":
        """Size a pool from a ``TransformerLM``'s own geometry: the
        K/V heads and head size its first block says it keeps."""
        import jax

        from ..models.generate import _check_model

        first, count = _check_model(model)
        k = jax.eval_shape(lambda: model.modules[first].state_init(
            1, "float32", page_size))["k"]
        return cls(num_pages, count, k.shape[1], page_size, k.shape[3],
                   dtype=dtype)

    def arena_bytes(self) -> int:
        """Bytes the full K+V arena occupies (itemsize from dtype;
        default float32)."""
        import numpy as np

        itemsize = np.dtype(self.dtype or np.float32).itemsize
        per = (self.layers * self.num_kv_heads * self.page_size
               * self.head_dim * itemsize)
        return 2 * self.num_pages * per

    def pages_for_tokens(self, tokens: int) -> int:
        return -(-int(tokens) // self.page_size)

    @property
    def max_positions(self) -> int:
        return self.num_pages * self.page_size

    # ------------------------------------------------------------ arena
    def _ensure_arena(self):
        if self._arena_k is None:
            import jax.numpy as jnp

            shape = (self.num_pages, self.layers, self.num_kv_heads,
                     self.page_size, self.head_dim)
            dt = self.dtype or jnp.float32
            self._arena_k = jnp.zeros(shape, dt)
            self._arena_v = jnp.zeros(shape, dt)

    @property
    def arena(self):
        """(arena_k, arena_v) — built on first access."""
        self._ensure_arena()
        return self._arena_k, self._arena_v

    def set_arena(self, arena_k, arena_v):
        """Install the functionally-updated arena (decoder-side; call
        under ``arena_lock``)."""
        self._arena_k = arena_k
        self._arena_v = arena_v

    def read_pages(self, page_ids):
        """Host copies of the given pages: (k, v) each
        ``[n, layers, Hkv, page_size, Dh]`` — the prefill→decode
        handoff export."""
        import numpy as np

        self._ensure_arena()
        idx = np.asarray(list(page_ids), np.int32)
        with self.arena_lock:
            return (np.asarray(self._arena_k[idx]),
                    np.asarray(self._arena_v[idx]))

    def write_pages(self, page_ids, k_pages, v_pages):
        """Scatter handed-off page contents into this pool's arena
        (decode-side import)."""
        import jax.numpy as jnp
        import numpy as np

        self._ensure_arena()
        idx = np.asarray(list(page_ids), np.int32)
        if k_pages.shape[0] != idx.shape[0]:
            raise ValueError(
                f"{k_pages.shape[0]} pages of data for {idx.shape[0]} "
                f"page ids")
        with self.arena_lock:
            dt = self._arena_k.dtype
            self._arena_k = self._arena_k.at[idx].set(
                jnp.asarray(k_pages, dt))
            self._arena_v = self._arena_v.at[idx].set(
                jnp.asarray(v_pages, dt))

    # ------------------------------------------------------------ alloc
    def set_owner_budget(self, owner: str, pages: int) -> None:
        """Cap ``owner`` at ``pages`` held pages — allocations past the
        cap raise :class:`PoolExhausted` even with free pages, so the
        over-budget owner sheds typed while other owners keep the
        arena."""
        with self._lock:
            self._budgets[str(owner)] = int(pages)

    def owner_held(self, owner: str) -> int:
        with self._lock:
            return self._held.get(str(owner), 0)

    def _take(self, n: int, owner: Optional[str] = None) -> List[int]:
        if owner is None:
            owner = self.default_owner
        with self._lock:
            if owner is not None:
                held = self._held.get(owner, 0)
                budget = self._budgets.get(owner)
                if budget is not None and held + n > budget:
                    self.exhaustions += 1
                    raise PoolExhausted(
                        f"owner {owner!r} needs {n} page(s) but holds "
                        f"{held} of its {budget}-page budget")
            if n > len(self._free):
                self.exhaustions += 1
                raise PoolExhausted(
                    f"need {n} page(s), {len(self._free)} free of "
                    f"{self.num_pages}")
            pages, self._free = self._free[:n], self._free[n:]
            self.allocs += n
            if owner is not None:
                self._held[owner] = self._held.get(owner, 0) + n
            in_use = self.num_pages - len(self._free)
            self.high_water = max(self.high_water, in_use)
            return pages

    def _give(self, pages: List[int],
              owner: Optional[str] = None) -> None:
        with self._lock:
            self._free.extend(pages)
            self.frees += len(pages)
            if owner is not None and owner in self._held:
                self._held[owner] = max(
                    0, self._held[owner] - len(pages))

    def alloc(self, n: int, owner: Optional[str] = None) -> PageLease:
        """Lease ``n`` pages (raises :class:`PoolExhausted` when the
        free list cannot cover it — shed, don't wait).  ``owner``
        (default: the pool's ``default_owner``) is charged for the
        pages against its optional budget."""
        if owner is None:
            owner = self.default_owner
        return PageLease(self, self._take(n, owner), owner)

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def occupancy(self) -> float:
        return 1.0 - self.free_pages / self.num_pages

    def stats(self) -> dict:
        with self._lock:
            free = len(self._free)
            by_owner = {o: h for o, h in self._held.items() if h}
        return {
            "by_owner": by_owner,
            "num_pages": self.num_pages,
            "free_pages": free,
            "in_use": self.num_pages - free,
            "occupancy": 1.0 - free / self.num_pages,
            "page_size": self.page_size,
            "allocs": self.allocs,
            "frees": self.frees,
            "exhaustions": self.exhaustions,
            "high_water": self.high_water,
            "arena_bytes": self.arena_bytes(),
        }
