"""KV state behind the serving engine's ``prefill -> insert -> generate``
stages: fixed-size paged blocks with a free-list allocator, plus the legacy
dense per-slot rectangle behind the same interface.

Paged layout
------------
One shared pool of ``num_pages`` pages per layer (page 0 is a reserved
*sink*: unallocated page-table entries point there, so ride-along writes
from free slots can never corrupt a live page).  Each slot owns an ordered
page list; the host-side page table (slots, pages_per_slot) int32 maps
logical page -> pool page and ships to the device before every decode
chunk.  Eviction frees pages back to the free list instead of abandoning a
``max_len`` rectangle, and :meth:`PagedKV.insert_shared` makes a prefix-
cache hit a page-table splice — shared full pages are refcounted, only the
partial tail page (where decode writes land) is copied per slot.

Pool leaves: ``k`` and ``v`` (L, num_pages, page_size, Hkv, dh) for
grouped-query attention; for latent attention the rank+rope rows split in
two, ``lat`` (L, num_pages, page_size, rank) and ``rope``, where ``pack``
pages' rope parts share a 128-lane row (see
:meth:`repro.models.layers.LatentAttention.init_paged_cache`), read by the
decode step through the same page tables.  A prefill cache's latent rows
are split the same way where they enter the pool (:func:`_pool_rows`).

Numerics: on the gather path the decode step gathers each slot's pages
into a dense view and slices it back to ``max_len`` (see ``kv_limit`` in
the model layer), so paged serving is token-for-token — bitwise — equal to
the dense layout.  On the kernel path (where Pallas compiles, see
:func:`repro.models.layers.attention_path`) the step reads only each
slot's live pages through the page table, and equals the dense layout
within float tolerance, not bitwise.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PageAllocator", "PageExhausted", "PagedKV", "DenseKV", "Prefix"]


def _pool_rows(pool: dict, kv: dict) -> dict:
    """A prefill cache's rows (L, B, S, ...) under each pool leaf's name
    (its ``length`` is left out): the latent pool keeps a prefill's
    rank+rope rows ``lat`` as its two leaves ``lat`` and ``rope``."""
    if "rope" not in pool:
        return {name: kv[name] for name in pool}
    r = pool["lat"].shape[-1]
    return {"lat": kv["lat"][..., :r], "rope": kv["lat"][..., r:]}


def _put_page(leaf, rows, page):
    """Write one page's rows (L, 1, page_size, ..., w) into pool page
    ``page`` of ``leaf``, in place where ``leaf`` is donated.  Where the
    rows are narrower than the leaf's last dimension, ``pack`` pages lie
    side by side along it: page p at row p // pack, from lane (p % pack) *
    w."""
    w = rows.shape[-1]
    pack = leaf.shape[-1] // w
    start = (0, page) + (0,) * (leaf.ndim - 2)
    if pack > 1:
        start = (0, page // pack) + start[2:-1] + (page % pack * w,)
    return jax.lax.dynamic_update_slice(leaf, rows.astype(leaf.dtype), start)


class PageExhausted(RuntimeError):
    """The page pool has fewer free pages than an allocation needs."""


@dataclass
class Prefix:
    """Result of :meth:`Engine.prefill` — everything ``insert`` needs.

    Exactly one of ``kv`` (bucketed dense prefill cache, possibly B rows)
    or ``pages``/``tail`` (prefix-cache materialization, B=1: shared full
    pages + the partial-tail page KV) is set.
    """
    lengths: np.ndarray                  # (B,) true prompt lengths
    first_tokens: np.ndarray             # (B,) greedy token at each last position
    bucket: int                          # padded prefill length (pow2 bucket)
    kv: dict | None = None               # {"k": (L,B,pb,H,dh), "v": ..., "length"}
    pages: list[int] | None = None       # shared full pages (prefix cache)
    tail: dict | None = None             # pool leaf -> its (L, page_size, ...) rows
    cached: bool = False                 # True when served from the PrefixCache
    rid: int = -1                        # request id, for per-request spans

    @property
    def batch(self) -> int:
        return int(len(self.lengths))


class PageAllocator:
    """Free-list page allocator with refcounts for shared (prefix) pages.

    Page 0 is never handed out — it is the sink page free slots' tables
    point at.  ``alloc`` is all-or-nothing; ``free`` decrefs and returns a
    page to the free list when its last owner lets go, so the list reuses
    recently-freed pages first (LIFO).
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is the sink)")
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))   # pop() -> page 1 first
        self._refs: dict[int, int] = {}
        self.peak_used = 0

    @property
    def used(self) -> int:
        return len(self._refs)

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, n: int = 1) -> list[int]:
        if n > len(self._free):
            raise PageExhausted(
                f"need {n} page(s), {len(self._free)} free of "
                f"{self.num_pages - 1} usable")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        self.peak_used = max(self.peak_used, self.used)
        return pages

    def incref(self, pages) -> None:
        for p in pages:
            self._refs[p] += 1

    def free(self, pages) -> None:
        for p in pages:
            r = self._refs[p] - 1
            if r:
                self._refs[p] = r
            else:
                del self._refs[p]
                self._free.append(p)

    def report(self) -> dict:
        return {"num_pages": self.num_pages - 1, "used": self.used,
                "free": self.free_count, "peak_used": self.peak_used}


class PagedKV:
    """Paged slot KV: page pool on device, page tables on the host.

    ``reclaim`` (set by the engine when a prefix cache is attached) is
    called on pool pressure; it should release at least one page and return
    True, or False when nothing can be evicted.
    """

    def __init__(self, model, slots: int, max_len: int, page_size: int,
                 num_pages: int | None = None,
                 reclaim: Callable[[], bool] | None = None):
        if model.init_paged_cache is None:
            raise NotImplementedError(
                f"family {model.cfg.family!r} has no paged KV layout")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.model = model
        self.slots = slots
        self.max_len = max_len
        self.page_size = page_size
        self.pages_per_slot = math.ceil(max_len / page_size)
        # worst case (every slot filled to max_len) + the sink page, unless
        # the caller over/under-subscribes explicitly
        self.num_pages = (num_pages if num_pages is not None
                          else slots * self.pages_per_slot + 1)
        self.allocator = PageAllocator(self.num_pages)
        self.reclaim = reclaim
        self.pool = model.init_paged_cache(self.num_pages, page_size)
        self.table = np.zeros((slots, self.pages_per_slot), np.int32)
        self.lengths = np.zeros((slots,), np.int32)
        self._slot_pages: list[list[int]] = [[] for _ in range(slots)]
        self._splice_fns: dict[tuple, Callable] = {}
        self._tail_fn: Callable | None = None

    # -- allocation ------------------------------------------------------------
    def _alloc(self, n: int) -> list[int]:
        while True:
            try:
                return self.allocator.alloc(n)
            except PageExhausted:
                if self.reclaim is None or not self.reclaim():
                    raise

    # -- decode plumbing -------------------------------------------------------
    def decode_cache(self) -> dict:
        """The decode-step cache pytree; page table and lengths are pushed
        fresh from the host so evictions take effect before the next chunk.
        Host arrays are COPIED at the boundary: ``jnp.asarray`` may
        zero-copy-alias a numpy buffer (CPU backend), and the host mutates
        ``table``/``lengths`` while the async decode chunk is in flight."""
        return {**self.pool,
                "page_table": jnp.asarray(self.table.copy()),
                "length": jnp.asarray(self.lengths.copy())}

    def absorb(self, new_cache: dict) -> None:
        """Take the pool back from a decode chunk's output cache."""
        self.pool = {name: new_cache[name] for name in self.pool}

    def live_pages(self, slots, steps: int) -> int:
        """Pages per layer that ``steps`` decode steps read below the
        occupied ``slots``' lengths, summed over slots and steps: what the
        paged kernel copies (``slots * pages_per_slot`` a step is what the
        gather path reads)."""
        ps = self.page_size
        return sum(-(-(int(self.lengths[s]) + i) // ps)
                   for s in slots for i in range(steps))

    def advance(self, slots, steps: int) -> None:
        """Host-side length bookkeeping after a decode chunk."""
        for s in slots:
            self.lengths[s] += steps

    def ensure(self, slot: int, n_tokens: int) -> None:
        """Grow ``slot``'s page list to cover ``n_tokens`` more positions."""
        need = min(math.ceil((int(self.lengths[slot]) + n_tokens)
                             / self.page_size), self.pages_per_slot)
        owned = self._slot_pages[slot]
        while len(owned) < need:
            page = self._alloc(1)[0]
            self.table[slot, len(owned)] = page
            owned.append(page)

    # -- insert / free ---------------------------------------------------------
    def _splice_fn(self, pb: int, n: int) -> Callable:
        """Jitted pool write: row ``row`` of a (L, B, pb, ...) prefill
        cache -> ``n`` pool pages of every pool leaf (one compile per
        (bucket, page count)).  The pool is donated and written page by
        page in place: a copy, or the relayout a scatter over the page
        axis of the latent pool asks for, would need a second pool's worth
        of device memory."""
        fn = self._splice_fns.get((pb, n))
        if fn is None:
            ps = self.page_size

            def splice(pool, src, row, pages):
                need = n * ps
                out = {}
                for name, rows in _pool_rows(pool, src).items():
                    sel = jax.lax.dynamic_index_in_dim(
                        rows, row, axis=1, keepdims=False)  # (L, pb, ...)
                    pad = [(0, 0)] * sel.ndim
                    if need > pb:
                        pad[1] = (0, need - pb)
                        sel = jnp.pad(sel, pad)
                    else:
                        sel = sel[:, :need]
                    pg = sel.reshape(sel.shape[0], n, ps, *sel.shape[2:])

                    def put(i, acc, pg=pg):
                        page = jax.lax.dynamic_slice_in_dim(pg, i, 1, axis=1)
                        return _put_page(acc, page, pages[i])

                    out[name] = jax.lax.fori_loop(0, n, put, pool[name])
                return out

            fn = jax.jit(splice, donate_argnums=(0,))
            self._splice_fns[(pb, n)] = fn
        return fn

    def _bucket(self, kv: dict) -> int:
        return kv[next(iter(self.pool))].shape[2]

    def insert_kv(self, kv: dict, row: int, true_len: int, slot: int) -> None:
        """Private-page insert: splice one prefill row into freshly
        allocated pages (the page-table splice that replaces the dense
        cache re-home)."""
        n = max(1, math.ceil(true_len / self.page_size))
        pages = self._alloc(n)
        self.pool = self._splice_fn(self._bucket(kv), n)(
            self.pool, kv, jnp.asarray(row, jnp.int32),
            jnp.asarray(pages, jnp.int32))
        self._set_slot(slot, pages, true_len)

    def insert_shared(self, pages: list[int], tail, true_len: int,
                      slot: int) -> None:
        """Prefix-cache insert: point the slot's table at the shared full
        pages (refcounted — never written again) and copy only the partial
        tail page, where this request's decode writes will land."""
        self.allocator.incref(pages)
        owned = list(pages)
        if tail is not None:
            tp = self._alloc(1)[0]
            if self._tail_fn is None:
                self._tail_fn = jax.jit(lambda pool, rows, p: {
                    name: _put_page(leaf, rows[name][:, None], p)
                    for name, leaf in pool.items()}, donate_argnums=(0,))
            self.pool = self._tail_fn(self.pool, tail,
                                      jnp.asarray(tp, jnp.int32))
            owned.append(tp)
        self._set_slot(slot, owned, true_len)

    def _set_slot(self, slot: int, pages: list[int], true_len: int) -> None:
        if self._slot_pages[slot]:
            raise RuntimeError(f"slot {slot} already holds pages")
        self.table[slot] = 0
        self.table[slot, :len(pages)] = pages
        self.lengths[slot] = true_len
        self._slot_pages[slot] = pages

    def free(self, slot: int) -> None:
        """Eviction frees pages (decref for shared ones), not a rectangle."""
        if self._slot_pages[slot]:
            self.allocator.free(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self.table[slot] = 0
        self.lengths[slot] = 0

    # -- prefix-cache materialization ------------------------------------------
    def materialize_prefix(self, kv: dict, row: int, true_len: int):
        """Copy a prefill row into cache-owned pool pages: returns
        (full_pages, tail) where ``full_pages`` covers the ``true_len //
        page_size`` complete pages and ``tail`` is the partial page's rows
        of each pool leaf as plain device arrays (no pool page held)."""
        ps = self.page_size
        n_full = true_len // ps
        pb = self._bucket(kv)
        pages: list[int] = []
        if n_full:
            pages = self._alloc(n_full)
            self.pool = self._splice_fn(pb, n_full)(
                self.pool, kv, jnp.asarray(row, jnp.int32),
                jnp.asarray(pages, jnp.int32))
        tail = None
        if true_len % ps:
            lo = n_full * ps
            pad = max(0, lo + ps - pb)
            tail = {}
            for name, rows in _pool_rows(self.pool, kv).items():
                t = rows[:, row, lo:lo + ps]
                if pad:
                    t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                tail[name] = t
        return pages, tail

    def release_pages(self, pages: list[int]) -> None:
        self.allocator.free(pages)

    def report(self) -> dict:
        rep = self.allocator.report()
        rep.update(page_size=self.page_size,
                   pages_per_slot=self.pages_per_slot,
                   occupancy=rep["used"] / max(rep["num_pages"], 1),
                   slot_pages=[len(p) for p in self._slot_pages])
        return rep


class DenseKV:
    """The legacy dense slot cache — (L, slots, max_len, H, dh) rectangles —
    behind the same insert/ensure/free surface, kept for mesh-sharded
    engines (the page pool is not slot-partitionable) and as the equality
    reference for the paged path."""

    def __init__(self, model, slots: int, max_len: int):
        self.model = model
        self.slots = slots
        self.max_len = max_len
        cache = dict(model.init_cache(slots, max_len))
        cache["length"] = jnp.zeros((slots,), jnp.int32)
        self.cache = cache
        self._write_fns: dict[int, Callable] = {}

    def decode_cache(self) -> dict:
        return self.cache

    def absorb(self, new_cache: dict) -> None:
        self.cache = new_cache

    def advance(self, slots, steps: int) -> None:
        pass                      # device-side length is authoritative

    def ensure(self, slot: int, n_tokens: int) -> None:
        pass                      # every slot owns its max_len rectangle

    def _write_fn(self, pb: int) -> Callable:
        """Jitted slot write: splice row ``row`` of a (·, B, pb, ·, ·)
        prefill cache into row ``slot`` of the batched decode cache (traced
        indices — one compile per bucket, not per slot)."""
        fn = self._write_fns.get(pb)
        if fn is None:
            def write(cache, pcache, row, slot):
                out = dict(cache)
                for k, leaf in cache.items():
                    if k == "length":
                        continue
                    upd = jax.lax.dynamic_index_in_dim(
                        pcache[k], row, axis=1, keepdims=True).astype(leaf.dtype)
                    start = (0, slot) + (0,) * (leaf.ndim - 2)
                    out[k] = jax.lax.dynamic_update_slice(leaf, upd, start)
                out["length"] = cache["length"].at[slot].set(
                    pcache["length"][row])
                return out
            fn = jax.jit(write)
            self._write_fns[pb] = fn
        return fn

    def insert_kv(self, kv: dict, row: int, true_len: int, slot: int) -> None:
        pb = next(v for k, v in kv.items() if k != "length").shape[2]
        self.cache = self._write_fn(pb)(
            self.cache, kv, jnp.asarray(row, jnp.int32),
            jnp.asarray(slot, jnp.int32))

    def free(self, slot: int) -> None:
        pass                      # admission's slot write resets KV + length

    def report(self) -> dict:
        return {"layout": "dense", "slots": self.slots,
                "max_len": self.max_len}
