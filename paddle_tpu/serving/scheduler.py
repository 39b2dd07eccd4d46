"""Continuous-batching request scheduler (vLLM/Orca-style iteration-level
scheduling, reduced to the static-slot model the TPU decode core wants).

Requests queue FIFO; the engine admits one into a KV-cache slot the moment
the slot frees — mid-run, between decode steps — instead of waiting for the
whole batch to drain (the static-batching failure mode where one long
generation holds B-1 idle slots hostage). Queue depth / slot occupancy are
exported through paddle_tpu.observability when FLAGS_observability is on.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from typing import Deque, Dict, List, Optional

from ..observability import metrics as _metrics
from .sampling import SamplingParams

QUEUED = "queued"
RUNNING = "running"
FINISHED = "finished"

_req_counter = itertools.count()


class Request:
    """One generation request: prompt ids + SamplingParams + accumulated
    output. ``finish_reason`` is ``eos`` | ``length`` | ``cache_full``."""

    def __init__(self, prompt_ids, sampling: Optional[SamplingParams] = None,
                 request_id: Optional[int] = None):
        self.request_id = next(_req_counter) if request_id is None else request_id
        self.prompt_ids = [int(t) for t in prompt_ids]
        if not self.prompt_ids:
            raise ValueError("empty prompt")
        self.sampling = sampling or SamplingParams()
        self.output_ids: List[int] = []
        self.state = QUEUED
        self.finish_reason: Optional[str] = None
        self.slot: Optional[int] = None
        # serving-tier bookkeeping (prefix cache / speculative decoding);
        # rides into the request-trace records for TTFT attribution
        self.prefix_hit_blocks = 0
        self.draft_tokens = 0
        self.accepted_tokens = 0
        # timing (host clocks; feed the ttft/tpot histograms)
        self.arrival_time = time.perf_counter()
        self.admit_time: Optional[float] = None  # popped from the queue
        self.first_token_time: Optional[float] = None
        self.finish_time: Optional[float] = None

    @property
    def num_generated(self) -> int:
        return len(self.output_ids)

    def __repr__(self):
        return (f"Request(id={self.request_id}, state={self.state}, "
                f"prompt={len(self.prompt_ids)} toks, "
                f"generated={self.num_generated})")


class PageAllocator:
    """Refcounted free-list allocator over the paged KV cache's page pool.

    Page ids run ``[1, num_pages)`` — page 0 is the reserved trash page
    that sentinel table entries clamp to (kv_cache.PAGE_SENTINEL) and is
    never handed out. ``alloc`` is all-or-nothing: a request either gets
    every page it asked for or the pool state is untouched and the caller
    backpressures (leaves the request queued / finishes it ``cache_full``).
    Double-allocation and double-free are hard errors, not best-effort —
    the exact-cover invariant (every page is free XOR referenced, and a
    page returns to the free list exactly when its last reference drops)
    is what tests/test_paged_kv.py and tests/test_prefix_spec.py pin.

    Copy-on-write sharing rides the refcounts: the prefix cache ``retain``s
    a page per sharer (trie leaf, each splice), each sharer ``free``s its
    own reference at finish, and the page stays live until the count hits
    zero. A writer must never touch a page with ``is_shared()`` true — it
    allocates a private copy first (PagedKVCache.copy_page) and frees its
    reference on the shared original.

    Occupancy is exported through ``serving.kv.pages.{allocated,free}`` and
    ``serving.kv.page_utilization`` when FLAGS_observability is on.
    """

    def __init__(self, num_pages: int, group: Optional[str] = None):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (trash page + 1)")
        self.num_pages = num_pages
        #: the page group it serves, where a cache has several: its gauges
        #: carry ``group=<name>`` (None: the one allocator there always was)
        self.group = group
        # pop() from the tail hands out the lowest free id first
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        self._owners: Dict[int, List[str]] = {}
        self._export_gauges()

    @property
    def num_allocatable(self) -> int:
        return self.num_pages - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocated(self) -> int:
        return len(self._refs)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def is_shared(self, page: int) -> bool:
        return self._refs.get(page, 0) > 1

    @property
    def num_shared(self) -> int:
        return sum(1 for c in self._refs.values() if c > 1)

    def alloc(self, n: int, owner: Optional[str] = None) -> Optional[List[int]]:
        """``n`` fresh page ids at refcount 1, or None (pool unchanged) if
        fewer than ``n`` are free. ``owner`` is a debug label (slot/request)
        echoed back by double-free errors."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
            self._owners[p] = [owner] if owner is not None else []
        self._export_gauges()
        return pages

    def retain(self, pages: List[int], owner: Optional[str] = None):
        """Add one reference per page (a new sharer of already-live pages —
        a prefix-cache splice or trie insertion). Retaining a page that was
        never handed out is the same class of bug as double-free."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(
                    f"retain of page {p} which is not allocated"
                    + (f" (by {owner})" if owner is not None else ""))
        for p in pages:
            self._refs[p] += 1
            if owner is not None:
                self._owners[p].append(owner)
        self._export_gauges()

    def free(self, pages: List[int], owner: Optional[str] = None):
        """Drop one reference per page; a page rejoins the free list only
        when its last reference goes. Freeing an unreferenced page raises
        with the full offender list and the owners on record, so a
        double-free names who it collided with instead of just failing."""
        bad = [p for p in pages if p not in self._refs]
        if bad:
            known = {p: list(self._owners.get(p, [])) for p in bad}
            raise ValueError(
                f"free of page(s) {bad} not allocated (double-free "
                f"or never handed out); freed by {owner!r}, last known "
                f"owners: {known}")
        for p in pages:
            self._refs[p] -= 1
            if owner is not None and owner in self._owners[p]:
                self._owners[p].remove(owner)
            if self._refs[p] == 0:
                del self._refs[p]
                del self._owners[p]
                self._free.append(p)
        self._free.sort(reverse=True)
        self._export_gauges()

    def _export_gauges(self):
        if not _metrics.enabled():
            return
        labels = {} if self.group is None else {"group": self.group}
        _metrics.gauge("serving.kv.pages.allocated", len(self._refs), **labels)
        _metrics.gauge("serving.kv.pages.free", len(self._free), **labels)
        _metrics.gauge("serving.kv.page_utilization",
                       len(self._refs) / max(1, self.num_allocatable),
                       **labels)


class Scheduler:
    """FIFO waiting queue + fixed slot table of size ``num_slots``."""

    def __init__(self, num_slots: int):
        self.num_slots = num_slots
        self.waiting: Deque[Request] = deque()
        self.running: List[Request] = []

    def add(self, request: Request):
        request.state = QUEUED
        self.waiting.append(request)
        _metrics.counter("serving.requests", 1, event="added")
        self._export_gauges()

    def next_waiting(self) -> Optional[Request]:
        """Pop the request the engine should admit next (None when the queue
        is empty). The engine pairs it with a freshly allocated slot."""
        if not self.waiting:
            return None
        req = self.waiting.popleft()
        req.admit_time = time.perf_counter()
        req.state = RUNNING
        self.running.append(req)
        self._export_gauges()
        return req

    def finish(self, request: Request, reason: str):
        request.state = FINISHED
        request.finish_reason = reason
        request.finish_time = time.perf_counter()
        self.running.remove(request)
        _metrics.counter("serving.requests", 1, event="finished")
        _metrics.counter("serving.finish_reason", 1, reason=reason)
        if request.first_token_time is not None and request.num_generated > 1:
            tpot = ((request.finish_time - request.first_token_time)
                    / (request.num_generated - 1))
            _metrics.histogram("serving.tpot.seconds", tpot)
        self._export_gauges()

    def observe_decode_step(self, request: Request, seconds: float):
        """Per-step inter-token latency for one RUNNING request — the
        finish-time tpot averages a whole generation, so a mid-request
        stall (one slow decode step) vanishes into it; this histogram is
        what the SLO monitor's decode_step check reads."""
        _metrics.histogram("serving.decode.token.seconds", seconds)

    @property
    def has_unfinished(self) -> bool:
        return bool(self.waiting or self.running)

    def _export_gauges(self):
        if not _metrics.enabled():
            return
        _metrics.gauge("serving.queue.depth", len(self.waiting))
        _metrics.gauge("serving.requests.active",
                       len(self.waiting) + len(self.running))
        _metrics.gauge("serving.slots.active", len(self.running))
        _metrics.gauge("serving.slots.occupancy",
                       len(self.running) / max(1, self.num_slots))
