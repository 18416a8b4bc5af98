"""Paged KV-cache pool: capacity accounting for the serving engine.

The physical layout stays the static-length dense cache the models
already decode against — K and V a layer that attends, `[B, S_max, H_kv, D]`
(`models/llama.py`) or `[B, S_max, D]` at one KV head (`models/jamba.py`),
one row per batch slot. What this pool manages is the CAPACITY of that
layout: each slot's S_max positions are divided into fixed-size pages, and
a request must hold enough pages for its whole lifetime (prompt +
max_new_tokens) before it may occupy a slot. A recurrent layer's state (a
conv window, an SSM state) is no page, and neither is a sliding-window
layer's ring of its last positions (`models/mimo.py`): each is a fixed cost
a slot whatever the request's length, which `info()` reports beside the
pages (`slot_state_bytes`, `window_bytes_per_slot`, with `page_bytes`); a
page budget that trades the one
against the other is later work (ROADMAP A3). That gives vLLM-style capacity-based
admission without a gather kernel: admission is all-or-nothing, so an
admitted request can never stall mid-decode waiting for memory, and the
no-preemption invariant keeps the decode path retrace-free.

Pages are ref-counted (retain/release): the substrate prefix sharing now
spends (`prefix.py`) — a borrower takes refs on a donor's prompt pages
via `share()`, which accepts only pages `commit()`ed by a COMPLETED
prefill (the typed `PageUncommitted` guards the fork-during-prefill
race). A page returns to the free list only when its last holder
releases it — and loses its committed mark there, so a recycled page is
never shareable before its new prefill commits. `info()` exposes the
counters the deadline tests assert on (an expired request's pages must
land back in `free_pages`).
"""
from __future__ import annotations

import threading
from typing import List


class PageUncommitted(RuntimeError):
    """Typed rejection of `share()` on a page whose KV rows are still being
    written (an in-flight bucketed or chunked prefill owns it). Only
    COMMITTED full pages may enter the prefix-sharing radix tree: a fork
    taken mid-prefill would hand the borrower rows the donor has not
    finished computing (the fork-during-prefill race)."""

    def __init__(self, page: "Page"):
        self.page = page
        super().__init__(
            f"page {page.pid} is not committed (an in-flight prefill is "
            f"still writing it) — only committed full pages are shareable")


class PoolExhausted(RuntimeError):
    """Admission failed: not enough free KV pages for the reservation.

    `permanent=True` means the reservation exceeds the pool's TOTAL
    capacity — no amount of waiting admits it (a sizing error, not
    backpressure), and the caller must not retry."""

    def __init__(self, need: int, free: int, total: int,
                 permanent: bool = False):
        self.need, self.free, self.total = need, free, total
        self.permanent = permanent
        tail = ("exceeds total capacity — the request can NEVER be "
                "admitted; resize the pool/engine"
                if permanent else
                "request stays queued until capacity returns")
        super().__init__(
            f"KV page pool exhausted: need {need} page(s), {free} free of "
            f"{total} total — {tail}")


class Page:
    """One fixed-size span of KV positions. Identity is the unit of
    accounting; the engine maps (slot, position) to pages implicitly
    through the dense layout."""

    __slots__ = ("pid", "refs", "committed")

    def __init__(self, pid: int):
        self.pid = pid
        self.refs = 0
        # a page is committed once the prefill that filled its KV rows has
        # completed; only then may share() hand it to another request
        self.committed = False

    def __repr__(self):
        return (f"Page({self.pid}, refs={self.refs}"
                f"{', committed' if self.committed else ''})")


class KVPagePool:
    """Free-list of `total_pages` pages of `page_size` tokens each."""

    def __init__(self, total_pages: int, page_size: int,
                 page_bytes: int = 0, slot_state_bytes: int = 0,
                 slot_window_bytes: int = 0):
        if total_pages < 1 or page_size < 1:
            raise ValueError("KVPagePool: total_pages/page_size must be >= 1")
        self.total_pages = int(total_pages)
        self.page_size = int(page_size)
        # what the accounting stands for in device memory (0: not told):
        # one page's K/V rows over every layer that keeps every position,
        # and what a slot holds beside its pages whatever its length:
        # recurrent state, and the rings of the sliding-window layers
        self.page_bytes = int(page_bytes)
        self.slot_state_bytes = int(slot_state_bytes)
        self.slot_window_bytes = int(slot_window_bytes)
        self._free: List[Page] = [Page(i) for i in range(total_pages)]
        self._lock = threading.Lock()
        self._allocs = 0
        self._releases = 0
        self._shared = 0
        self._peak_active = 0

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold `n_tokens` KV positions."""
        return -(-max(int(n_tokens), 1) // self.page_size)

    def alloc(self, n: int) -> List[Page]:
        """Take `n` pages off the free list at refcount 1, or raise the
        typed PoolExhausted without taking any (all-or-nothing)."""
        with self._lock:
            if n > len(self._free):
                raise PoolExhausted(n, len(self._free), self.total_pages)
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                p.refs = 1
            self._allocs += n
            active = self.total_pages - len(self._free)
            self._peak_active = max(self._peak_active, active)
            return pages

    def retain(self, pages: List[Page]):
        """Add a holder to already-allocated pages (prefix sharing)."""
        with self._lock:
            for p in pages:
                if p.refs < 1:
                    raise ValueError(f"retain of a free page: {p!r}")
                p.refs += 1

    def share(self, pages: List[Page]):
        """retain() restricted to COMMITTED pages — the prefix-sharing
        entry point. Raises the typed `PageUncommitted` (taking no refs)
        when any page is still being written by an in-flight prefill: a
        borrower must never fork onto half-written KV rows, so only pages
        `commit()`ed by a completed prefill are shareable. All-or-nothing,
        like alloc()."""
        with self._lock:
            for p in pages:
                if p.refs < 1:
                    raise ValueError(f"share of a free page: {p!r}")
                if not p.committed:
                    raise PageUncommitted(p)
            for p in pages:
                p.refs += 1
            self._shared += len(pages)

    def commit(self, pages: List[Page]):
        """Mark pages' KV rows durable (their prefill completed): from here
        on share() accepts them. Idempotent."""
        with self._lock:
            for p in pages:
                if p.refs < 1:
                    raise ValueError(f"commit of a free page: {p!r}")
                p.committed = True

    def release(self, pages: List[Page]):
        """Drop one holder; pages return to the free list at refcount 0
        (and lose their committed mark — the rows they accounted for are
        no longer anyone's)."""
        with self._lock:
            for p in pages:
                if p.refs < 1:
                    raise ValueError(f"double release: {p!r}")
                p.refs -= 1
                if p.refs == 0:
                    p.committed = False
                    self._free.append(p)
                    self._releases += 1

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def info(self) -> dict:
        """cache_info()-style introspection (asserted by the deadline and
        occupancy tests; surfaced in profiler.serving_summary())."""
        with self._lock:
            free = len(self._free)
            return {"total_pages": self.total_pages,
                    "page_size": self.page_size,
                    "page_bytes": self.page_bytes,
                    "slot_state_bytes": self.slot_state_bytes,
                    "window_bytes_per_slot": self.slot_window_bytes,
                    "free_pages": free,
                    "active_pages": self.total_pages - free,
                    "allocs": self._allocs,
                    "releases": self._releases,
                    "shared": self._shared,
                    "peak_active": self._peak_active}
