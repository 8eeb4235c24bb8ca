"""Paged KV cache: a page-pool + free-list allocator so serve slot count
and sequence length stop being compile-time constants.

The dense slot cache allocates ``slots x max_len`` KV rows up front — every
slot pays for the longest request the engine might ever see.  Paging (the
vLLM idea, fitted to this repo's layer-scanned cache layout) breaks the
cache into fixed ``page_size``-row pages in one physical pool:

  * each request owns just enough pages for its current depth, acquired
    from a host-side free list as decode crosses page boundaries;
  * the decode step receives a ``(slots, max_pages)`` page table; attention
    gathers each slot's logical view out of the pool and scatters the new
    token's K/V at its physical row (``models.attention``, paged branch);
  * physical page 0 is RESERVED as the null target: unallocated page-table
    entries point at it, inactive slots write their garbage row into it,
    and the per-row position masks keep it out of every softmax.

Exhaustion safety is the engine's contract, built on two pieces here: the
allocator *reports* exhaustion precisely (``PagesExhausted`` carries the
shortfall, nothing is half-allocated), and ownership is tracked per request
so preemption can free exactly one victim's pages.  The allocator is
host-side and deterministic (LIFO free list) — a replayed run allocates the
identical physical pages, which is what makes the ``page_exhaustion`` chaos
tests bit-reproducible.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig


class PagesExhausted(RuntimeError):
    """Raised by ``PageAllocator.alloc`` when the pool cannot satisfy the
    request.  Carries the shortfall so the engine can decide how many
    victims to preempt.  The failed alloc has NO side effects."""

    def __init__(self, needed: int, available: int):
        super().__init__(
            f"KV page pool exhausted: need {needed} pages, {available} free")
        self.needed = needed
        self.available = available


class PageAllocator:
    """Deterministic free-list allocator over physical page ids
    ``[first, first + total)``.

    Ownership is tracked per ``owner`` (the engine uses request ids): a page
    is either free or owned by exactly one live owner, and ``free_owner``
    returns every page an owner held — the preemption primitive.  The free
    list is LIFO so replayed runs hand out identical physical pages.
    """

    def __init__(self, total: int, *, first: int = 1):
        if total < 1:
            raise ValueError(f"page pool needs >= 1 page, got {total}")
        self.total = total
        self.first = first
        # LIFO: lowest ids come back out first (reversed push order).
        self._free: list[int] = list(range(first + total - 1, first - 1, -1))
        self._owned: dict[object, list[int]] = {}

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def live_owners(self) -> int:
        return len(self._owned)

    def owned(self, owner) -> list[int]:
        return list(self._owned.get(owner, ()))

    def alloc(self, n: int, owner) -> list[int]:
        """Acquire ``n`` pages for ``owner``; all-or-nothing."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            raise PagesExhausted(n, len(self._free))
        pages = [self._free.pop() for _ in range(n)]
        self._owned.setdefault(owner, []).extend(pages)
        return pages

    def free_owner(self, owner) -> list[int]:
        """Release every page ``owner`` holds (no-op for unknown owners);
        returns the released pages (the engine zeroes them on quarantine)."""
        pages = self._owned.pop(owner, [])
        self._free.extend(pages)
        return pages

    def check(self) -> None:
        """Invariant audit: no page is double-owned or both free and owned,
        and every page is accounted for.  Cheap (set arithmetic over ints);
        the property tests call it after every step."""
        owned = [p for pages in self._owned.values() for p in pages]
        owned_set = set(owned)
        if len(owned) != len(owned_set):
            raise AssertionError(f"page owned twice: {sorted(owned)}")
        free_set = set(self._free)
        if len(self._free) != len(free_set):
            raise AssertionError("free list holds duplicates")
        if owned_set & free_set:
            raise AssertionError(
                f"pages both free and owned: {sorted(owned_set & free_set)}")
        universe = set(range(self.first, self.first + self.total))
        if owned_set | free_set != universe:
            raise AssertionError(
                f"pages leaked: {sorted(universe - owned_set - free_set)}")


def pages_for(depth: int, page_size: int) -> int:
    """Pages needed to hold ``depth`` KV rows."""
    return -(-depth // page_size)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(pool: jax.Array, rows: jax.Array,
                  phys: jax.Array) -> jax.Array:
    """Write ``rows`` (L, S, KVH, D) into the flattened-row view of
    ``pool`` (L, P, page, KVH, D) at physical row indices ``phys`` (S,)."""
    l, p, page, kvh, d = pool.shape
    with jax.named_scope("kv_insert"):
        flat = pool.reshape(l, p * page, kvh, d)
        flat = flat.at[:, phys].set(rows.astype(flat.dtype))
        return flat.reshape(l, p, page, kvh, d)


@functools.partial(jax.jit, donate_argnums=(0,))
def _zero_pages(pool: jax.Array, pages: jax.Array) -> jax.Array:
    return pool.at[:, pages].set(0.0)


@dataclasses.dataclass
class PagedKV:
    """Device page pools + the host-side page table for one engine.

    ``k``/``v``: (L, num_pages, page_size, KVH, D) — same leaf structure as
    the dense cache (layer-stacked axis 0) so ``stack_cached`` scans it
    unchanged; only the per-layer shape differs.  ``table``: host
    (slots, max_pages) int32, logical page -> physical page, 0 = the
    reserved null page.
    """
    k: jax.Array
    v: jax.Array
    table: np.ndarray
    page_size: int

    @classmethod
    def build(cls, cfg: ModelConfig, *, slots: int, max_len: int,
              num_pages: int, page_size: int, dtype=None) -> "PagedKV":
        dtype = dtype or jnp.dtype(cfg.compute_dtype)
        shape = (cfg.num_layers, num_pages, page_size,
                 cfg.num_kv_heads, cfg.head_dim_)
        max_pages = pages_for(max_len, page_size)
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   table=np.zeros((slots, max_pages), np.int32),
                   page_size=page_size)

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    def cache(self) -> dict:
        """The cache dict the layer scan consumes (paged leaves)."""
        return {"k": self.k, "v": self.v}

    def update(self, new_cache: dict) -> None:
        self.k, self.v = new_cache["k"], new_cache["v"]

    def map_slot(self, slot: int, pages: list[int]) -> None:
        """Point ``slot``'s logical pages at ``pages`` (in logical order)."""
        self.table[slot, :] = 0
        self.table[slot, :len(pages)] = pages

    def extend_slot(self, slot: int, pages: list[int],
                    start_logical: int) -> None:
        self.table[slot, start_logical:start_logical + len(pages)] = pages

    def clear_slot(self, slot: int) -> None:
        self.table[slot, :] = 0

    def insert(self, slot: int, pages: list[int], k_rows: jax.Array,
               v_rows: jax.Array) -> None:
        """Prefill-insert: scatter ``k_rows``/``v_rows`` (L, S, KVH, D) —
        one request's freshly prefilled KV — into the pool and map the
        slot's table.  ``S <= len(pages) * page_size``; rows land at the
        pages' physical rows in logical order."""
        s = k_rows.shape[1]
        if s > len(pages) * self.page_size:
            raise ValueError(f"{s} rows > {len(pages)} pages "
                             f"x {self.page_size}")
        logical = np.arange(s)
        phys = (np.asarray(pages, np.int64)[logical // self.page_size]
                * self.page_size + logical % self.page_size)
        phys_j = jnp.asarray(phys, jnp.int32)
        self.k = _scatter_rows(self.k, k_rows, phys_j)
        self.v = _scatter_rows(self.v, v_rows, phys_j)
        self.map_slot(slot, pages)

    def zero_pages(self, pages: list[int]) -> None:
        """Zero page contents — required when quarantining possibly
        non-finite KV so a later occupant of the same physical pages can
        never contract against NaN rows (0 * finite is safe, 0 * NaN is
        not)."""
        if pages:
            idx = jnp.asarray(pages, jnp.int32)
            self.k = _zero_pages(self.k, idx)
            self.v = _zero_pages(self.v, idx)
