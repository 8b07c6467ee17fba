"""Content-hashed prefix cache: repeated prompts reuse KV pages.

Entries are keyed on the whole-prompt chained page hash
(:func:`repro.cache.token_prefix_keys`), so a hit means the *entire* token
sequence matched — insert is then an exact replay of the original prefill
state and trivially deterministic.  Each entry owns refcounted full pages
in the shared pool (never written after registration — decode writes land
on a per-slot private tail page, so no copy-on-write is needed) plus the
partial tail page's KV held as plain arrays outside the pool.

Because the fusion-plan cache keys on graph structure + shape bucket, a
prefix hit also reuses the cached prefill plan trivially (no prefill runs
at all); misses of the same bucket still share one plan.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

from repro.cache import token_prefix_keys

from .kv import PagedKV, Prefix

__all__ = ["PrefixCache", "PrefixEntry"]


@dataclass
class PrefixEntry:
    pages: list[int]          # cache-owned full pages in the pool
    tail: dict | None         # pool leaf -> partial-page rows, outside the pool
    length: int
    first_token: int


class PrefixCache:
    """LRU cache of materialized prompt KV, page-table spliced on hit."""

    def __init__(self, kv: PagedKV, max_entries: int = 64):
        self.kv = kv
        self.max_entries = max_entries
        self._entries: OrderedDict[str, PrefixEntry] = OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    def _key(self, tokens) -> str:
        return token_prefix_keys(tokens, self.kv.page_size)[-1]

    def lookup(self, tokens) -> Prefix | None:
        """Whole-prompt hit -> a ready-to-insert :class:`Prefix`, else None."""
        key = self._key(tokens)
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        return Prefix(
            lengths=np.array([entry.length], np.int32),
            first_tokens=np.array([entry.first_token], np.int64),
            bucket=entry.length,
            pages=entry.pages, tail=entry.tail, cached=True)

    def register(self, tokens, kv_cache: dict, row: int, first_token: int,
                 length: int) -> None:
        """Materialize one prefill row into cache-owned pages.  Best-effort:
        pool pressure (all pages pinned by live slots) skips registration
        rather than failing the prefill."""
        key = self._key(tokens)
        if key in self._entries:
            self._entries.move_to_end(key)
            return
        try:
            pages, tail = self.kv.materialize_prefix(kv_cache, row, length)
        except Exception:
            return
        self._entries[key] = PrefixEntry(pages=pages, tail=tail,
                                         length=length,
                                         first_token=int(first_token))
        while len(self._entries) > self.max_entries:
            self.evict_one()

    def evict_one(self) -> bool:
        """Drop the LRU entry, releasing its pool pages.  Used both for the
        entry cap and as the allocator's pressure-reclaim callback."""
        if not self._entries:
            return False
        _, entry = self._entries.popitem(last=False)
        if entry.pages:
            self.kv.release_pages(entry.pages)
        return True

    @property
    def pages_held(self) -> int:
        return sum(len(e.pages) for e in self._entries.values())

    def report(self) -> dict:
        total = self.hits + self.misses
        return {"hits": self.hits, "misses": self.misses,
                "hit_rate": self.hits / total if total else 0.0,
                "entries": len(self._entries),
                "pages_held": self.pages_held}
