"""The paged K/V cache's host side: one pool of pages per layer class
and two classes of page table.

A page holds ``page`` positions of K and V for every layer of its class
(the layers share page numbers; ``model.py::empty_cache`` holds one K and
one V array per layer and, for a model with an indexer, one array of its
keys on the same page numbers: a second pool of the full class, no class
of its own; for a model with latent attention ONE array a layer of the
latent's width in place of K and V, again on the full class's page
numbers: a latent is "the new positions' rows" like any K or V, so it
needs no class of table, only a pool of another width). Page 0 of each pool is never handed out: the step program
points unallocated table slots and padded rows there. A model without
window layers has no window pool, no window table and reserves nothing
of that class.

- **full class** (``full_attention`` layers): a sequence's table grows
  by a page whenever its positions pass a page boundary and keeps every
  page until the request ends.
- **window class** (``sliding_attention`` layers): a ring. The table
  holds the pages from the first one the band of the next query still
  touches up to the newest, at most ``ring`` = (window + chunk) / page of
  them; :meth:`SeqPages.trim` hands the pages that fell behind the
  window back to the pool inside the step that passed them. The step
  program gets the table from its first live page on with that page's
  position as ``base``, so it never visits a page behind the band.

Admission is by reservation: a request is admitted only if both pools
can hold it to its end beside everything already admitted
(:meth:`PagedCache.admit`), so nothing is ever evicted mid-request.
Where the full pool is smaller than ``rows`` longest requests (every
layer of the full class: 13 KB a position over six layers of K, V and
indexer keys for ``KeyeVL2``, 6.9 KB of latents for ``xing4_0``;
``afmoe``'s full class holds only its full layers, 2 KB each), it is the
pool and not the rows that bounds what is resident, and rows stand empty
while the next request waits for pages (the engine counts them).
A model with Gated DeltaNet layers (``qwen3_next``) has a class of
state besides the pages: a request's recurrent state and conv tail have
no positions, so it gets ONE slot (:class:`StateSlots`) from admission
to release, which the step program zeroes at the request's first chunk
and overwrites at every step (``model.py::empty_cache``). A request
holds a row from admission on (the engine reserves it before its first
chunk), so a request mid-prefill is among the rows and ``rows`` slots
serve them all; a request's slot is its row's. Its DeltaNet layers have
no pool and no table.
Used from the engine's thread alone; no lock.
"""

from __future__ import annotations

from collections import deque

import numpy as np

from vlog_tpu.lm.model import Geometry, LmConfig


class PagePool:
    def __init__(self, pages: int):
        """``pages`` 0: the class has no layer, no page and no cost."""
        if pages == 1 or pages < 0:
            raise ValueError("a pool needs page 0 and at least one more")
        self.capacity = max(pages - 1, 0)       # page 0 is never handed out
        self._free = list(range(pages - 1, 0, -1))
        self.reserved = 0

    @property
    def in_use(self) -> int:
        return self.capacity - len(self._free)

    def take(self) -> int:
        return self._free.pop()

    def give(self, page: int) -> None:
        self._free.append(page)


class StateSlots:
    """The recurrent-state class: ``slots`` slots (0: the model keeps no
    such state), one a request from admission to release."""

    def __init__(self, slots: int):
        self.capacity = slots
        self._used: set[int] = set()

    @property
    def in_use(self) -> int:
        return len(self._used)

    def take(self, slot: int) -> int:
        if not 0 <= slot < self.capacity or slot in self._used:
            raise ValueError(f"state slot {slot} is not free")
        self._used.add(slot)
        return slot

    def give(self, slot: int) -> None:
        self._used.discard(slot)


class SeqPages:
    """One admitted sequence's tables (see the module docstring) and,
    for a model with recurrent state, its slot."""

    def __init__(self, cache: "PagedCache", need_w: int, need_f: int,
                 slot: int | None = None):
        self._cache = cache
        self._need = (need_w, need_f)
        self.slot = slot
        self.full: list[int] = []
        self.win: deque[int] = deque()
        self.win_first = 0          # logical page of win[0]
        self.peak_window_pages = 0

    def extend(self, upto: int) -> None:
        """Pages for every position below ``upto``."""
        c = self._cache
        last = (upto - 1) // c.page
        while len(self.full) <= last:
            self.full.append(c.full.take())
        while c.ring and self.win_first + len(self.win) <= last:
            self.win.append(c.window.take())
        self.peak_window_pages = max(self.peak_window_pages, len(self.win))

    def trim(self, next_pos: int) -> int:
        """Give back the window pages that no query at or after
        ``next_pos`` can see; returns how many."""
        c = self._cache
        keep_from = max(0, (next_pos - c.window_len + 1) // c.page)
        freed = 0
        while self.win and self.win_first < keep_from:
            c.window.give(self.win.popleft())
            self.win_first += 1
            freed += 1
        return freed

    def tables(self) -> tuple[np.ndarray, int, np.ndarray]:
        """``(window table (ring,), its base position, full table
        (max_pages,))``, unallocated slots 0."""
        c = self._cache
        wtab = np.zeros(c.ring, np.int32)
        wtab[:len(self.win)] = self.win
        ftab = np.zeros(c.max_pages, np.int32)
        ftab[:len(self.full)] = self.full
        return wtab, self.win_first * c.page, ftab

    def release(self) -> int:
        c = self._cache
        freed = len(self.win) + len(self.full)
        for p in self.win:
            c.window.give(p)
        for p in self.full:
            c.full.give(p)
        self.win.clear()
        self.full.clear()
        c.window.reserved -= self._need[0]
        c.full.reserved -= self._need[1]
        self._need = (0, 0)
        if self.slot is not None:
            c.slots.give(self.slot)
            self.slot = None
        return freed


class PagedCache:
    def __init__(self, cfg: LmConfig, geo: Geometry):
        geo.check(cfg)
        self.page = geo.page
        self.window_len = cfg.sliding_window
        self.ring = geo.ring(cfg.sliding_window)
        self.max_pages = geo.max_pages
        self.context_cap = geo.context_cap
        self.window = PagePool(geo.window_pages)
        self.full = PagePool(geo.full_pages)
        self.slots = StateSlots(geo.rows if cfg.linear_layers else 0)

    def need(self, total_len: int) -> tuple[int, int]:
        pages = -(-total_len // self.page)
        return min(pages, self.ring), pages

    def fits_ever(self, total_len: int) -> bool:
        """Could an empty cache hold the request to its end?"""
        w, f = self.need(total_len)
        return (total_len <= self.context_cap and w <= self.window.capacity
                and f <= self.full.capacity)

    def admit(self, total_len: int, row: int = 0) -> SeqPages | None:
        """Reserve both pools for a request of ``total_len`` positions
        (prompt plus output) that will hold ``row``, and its state slot
        where the model keeps one; ``None`` while they cannot hold it."""
        w, f = self.need(total_len)
        if (self.window.reserved + w > self.window.capacity
                or self.full.reserved + f > self.full.capacity):
            return None
        self.window.reserved += w
        self.full.reserved += f
        return SeqPages(self, w, f, self.slots.take(row)
                        if self.slots.capacity else None)

    def state(self) -> dict:
        return {"slots": self.slots.capacity, "in_use": self.slots.in_use}

    def in_use(self) -> dict:
        return {"window": self.window.in_use, "full": self.full.in_use}

    def pools(self) -> dict:
        """Per class: pages that can be handed out, pages handed out and
        pages spoken for by the admitted requests."""
        return {name: {"capacity": p.capacity, "in_use": p.in_use,
                       "reserved": p.reserved}
                for name, p in (("window", self.window),
                                ("full", self.full))}
