"""Continuous-batching scheduler: join / evict BETWEEN decode steps.

The decode batch has `max_batch` slots. Between any two decode steps the
scheduler (1) evicts slots whose request completed (EOS / length) or ran
out of TTL, (2) expires queued requests past their deadline (typed
RequestTimeout, reserved pages returned to the pool), and (3) admits
queued requests into free slots — strict FIFO, gated on an all-or-nothing
KV-page reservation covering the request's whole lifetime, so an admitted
request never stalls mid-decode and nothing is ever preempted.

Joining is invisible to in-flight slots: every per-slot quantity (position
offset, ragged attention length, cache row, a recurrent layer's state) is
independent across the batch dimension, and the decode executable's signature is fixed at
[max_batch, 1] — a join changes the CONTENTS of an inactive slot, never
the avals, so no new lowering and bitwise-identical tokens for everyone
already decoding (tests/test_serving.py proves both).
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import List, Tuple

from ...observability import trace
from .kv_pool import KVPagePool, PoolExhausted
from .request import Request, RequestState


class ContinuousBatchingScheduler:
    def __init__(self, pool: KVPagePool, max_batch: int,
                 reserve_extra_tokens: int = 0):
        self.pool = pool
        self.max_batch = int(max_batch)
        # per-request reservation padding: a speculative engine's verify
        # window may write up to spec_k positions past the accepted cursor,
        # so those scratch positions are reserved with the lifetime — the
        # all-or-nothing / no-preemption contract covers them too
        self.reserve_extra = int(reserve_extra_tokens)
        self._queue: deque[Request] = deque()
        self._running: dict[int, Request] = {}   # slot -> request
        self._free_slots = list(range(self.max_batch - 1, -1, -1))
        self._lock = threading.Lock()
        # optional reclaim hook (engine wires the prefix cache's evict):
        # called with the page shortfall when an alloc fails, returns pages
        # freed; a positive return earns exactly one alloc retry, so cached
        # prefixes yield to admission pressure instead of wedging the queue
        self.reclaim = None
        self.counters = {"submitted": 0, "admitted": 0, "finished": 0,
                         "timed_out": 0, "evicted": 0, "rejected": 0}

    def _pages_needed(self, req: Request) -> int:
        """Pages the request must OWN: its whole lifetime minus the shared
        prefix chain it already holds refs on (prefix sharing — the saved
        pages are exactly the prefill it skips)."""
        return self.pool.pages_for(
            req.prompt.size + req.max_new_tokens + self.reserve_extra) \
            - len(req.shared_pages)

    def _alloc(self, need: int):
        """pool.alloc with one reclaim-assisted retry (see `reclaim`)."""
        try:
            return self.pool.alloc(need)
        except PoolExhausted:
            if self.reclaim is None:
                raise
            if self.reclaim(need - self.pool.free_pages) <= 0:
                raise
            return self.pool.alloc(need)

    def _release_all(self, req: Request) -> None:
        """Give back everything the request holds: its own reservation AND
        its refs on the shared prefix chain (the tree's own refs keep the
        cached pages alive; a chain page a peer still decodes against
        never reaches the free list — refcount law)."""
        if req.pages:
            self.pool.release(req.pages)
            req.pages = []
        if req.shared_pages:
            self.pool.release(req.shared_pages)
            req.shared_pages = []

    # ---- intake ----
    def submit(self, req: Request):
        """Enqueue; reserve KV pages eagerly when capacity allows (the
        capacity-based admission control — a reservation made while queued
        is what an expiring queued request gives back).

        Reservations stay FIFO-prefix-ordered: a request reserves only if
        everything AHEAD of it in the queue is already reserved. Otherwise
        a small request behind a blocked head could pin the very pages the
        head is waiting for — with no TTL that wedges the queue forever
        (head can't alloc, reserver behind it can't join past strict FIFO)."""
        need = self._pages_needed(req)
        if need > self.pool.total_pages:
            with self._lock:
                self.counters["rejected"] += 1
            # never-fits: NOT queued — permanent sizing error, don't retry
            raise PoolExhausted(need, self.pool.free_pages,
                                self.pool.total_pages, permanent=True)
        with self._lock:
            self.counters["submitted"] += 1
            if all(r.pages for r in self._queue):
                try:
                    req.pages = self._alloc(need)
                    req.scratch_reserved = self.reserve_extra > 0
                except PoolExhausted:
                    pass  # stays queued unreserved; retried at join passes
            self._queue.append(req)

    # ---- the between-steps pass ----
    def schedule(self) -> Tuple[List[Request], List[Request]]:
        """-> (joined, evicted). Called by the engine before every decode
        step; all state transitions happen here, on the host, while the
        device batch is quiescent."""
        joined, evicted = [], []
        with self._lock:
            # 1. evict completed / expired running slots
            for slot in sorted(self._running):
                req = self._running[slot]
                if req.finish_reason in ("eos", "length"):
                    req.finish(RequestState.FINISHED)
                    self.counters["finished"] += 1
                elif req.deadline.expired:
                    req.finish_reason = "ttl"
                    req.finish(RequestState.TIMED_OUT)
                    self.counters["timed_out"] += 1
                else:
                    continue
                del self._running[slot]
                self._free_slots.append(slot)
                self._release_all(req)
                self.counters["evicted"] += 1
                evicted.append(req)
                trace.event("scheduler.evict", rid=req.rid, slot=slot,
                            reason=req.finish_reason)
            # 2. expire queued requests (typed rejection, pages returned)
            still = deque()
            for req in self._queue:
                if req.deadline.expired:
                    self._release_all(req)
                    req.finish_reason = "ttl"
                    req.finish(RequestState.TIMED_OUT)
                    self.counters["timed_out"] += 1
                    evicted.append(req)
                else:
                    still.append(req)
            self._queue = still
            # 3. join — strict FIFO so a big head request cannot starve
            while self._free_slots and self._queue:
                head = self._queue[0]
                if not head.pages:
                    need = self._pages_needed(head)
                    try:
                        head.pages = self._alloc(need)
                        head.scratch_reserved = self.reserve_extra > 0
                    except PoolExhausted:
                        break
                self._queue.popleft()
                head.slot = self._free_slots.pop()
                head.state = RequestState.PREFILL
                self._running[head.slot] = head
                self.counters["admitted"] += 1
                joined.append(head)
                if trace.enabled():
                    # queue wait: submit to join, on the request's own clock
                    trace.event("scheduler.join", rid=head.rid,
                                slot=head.slot, pages=len(head.pages),
                                waited_ns=int(1e9 * (time.perf_counter()
                                                     - head.submit_time)))
        return joined, evicted

    # ---- overload control (engine degradation ladder) ----
    def backlog_tokens(self) -> int:
        """Tokens still owed to everything queued or running — the
        numerator of the engine's projected-queue-wait estimate (divided
        by the measured token rate it yields seconds of backlog)."""
        with self._lock:
            queued = sum(r.max_new_tokens for r in self._queue)
            running = sum(max(0, r.max_new_tokens - len(r.output_tokens))
                          for r in self._running.values())
            return queued + running

    def shed_reserve_extra(self) -> int:
        """Degradation-ladder lever: stop reserving the per-request verify
        scratch for future allocations AND give back the whole pages it
        added to every reservation already held (running or queued). A
        request whose scratch went back is marked `scratch_reserved=False`
        so the engine never runs a speculative verify that would write
        past capacity it no longer owns. Returns pages freed."""
        freed = 0
        with self._lock:
            extra, self.reserve_extra = self.reserve_extra, 0
            if not extra:
                return 0
            for req in list(self._running.values()) + list(self._queue):
                if not req.pages or not req.scratch_reserved:
                    continue
                total = int(req.prompt.size) + req.max_new_tokens
                n = min(self.pool.pages_for(total + extra)
                        - self.pool.pages_for(total), len(req.pages))
                if n > 0:
                    # the TAIL of the reservation: prompt-front pages may
                    # be committed into the prefix tree, scratch never is
                    tail, req.pages = req.pages[-n:], req.pages[:-n]
                    self.pool.release(tail)
                    freed += n
                req.scratch_reserved = False
        return freed

    def restore_reserve_extra(self, extra: int) -> None:
        """Exit the ladder level: future reservations cover verify scratch
        again. Requests admitted while shed keep `scratch_reserved=False`
        (their speculative window has no capacity) until they finish."""
        with self._lock:
            self.reserve_extra = int(extra)

    # ---- views ----
    def running(self) -> dict:
        with self._lock:
            return dict(self._running)

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def active(self) -> int:
        with self._lock:
            return len(self._running)

    @property
    def idle(self) -> bool:
        with self._lock:
            return not self._running and not self._queue

    def info(self) -> dict:
        with self._lock:
            return {**self.counters, "active": len(self._running),
                    "queued": len(self._queue),
                    "free_slots": len(self._free_slots)}
