"""Continuous-batching scheduler over the paged KV pool.

Host-side control plane: requests wait in a FIFO, get admitted into one of
`n_slots` fixed batch slots when a slot and enough pages are free, and
release everything on completion. Prefill is chunked: a request is
admitted with only its *first chunk's* pages; `prefill_progress[slot]`
tracks how many prompt tokens have been written, pages are granted
chunk-by-chunk via `grow_to`, and the engine batches chunks from several
slots with ongoing decode slots into one mixed step under a token budget.

Capacity is ensured every step through `grow_to`: a sequence crossing a
page boundary gets a fresh page from the free list; when the pool is
exhausted, cold unreferenced prefix-cache pages are evicted first (LRU —
the second-chance free list), and only then is the most-recently-admitted
active request preempted (recompute-style: its pages are released —
including a partially-prefilled prompt's — and it requeues at the front of
the FIFO with its progress reset, generation restarting from the prompt:
the vLLM-style answer to fragmentation-free oversubscription).

With `prefix_cache=True`, admission walks the prompt's chained page hashes
(serving/prefix_cache.py) and maps the longest cached prefix of *full*
pages straight into the request's page table (refcount +1 per shared page);
`lengths`/`prefill_progress` start at the hit length and only the uncached
tail is chunk-prefilled. At least one prompt token is always recomputed so
the last-token logits exist. Finished requests promote their full prompt
pages into the cache in `complete`. Releasing a slot — completion or
preemption — only ever *decrements* refcounts through the single `_release`
choke point: a shared page stays mapped for its other holders, and a cached
page whose last holder leaves parks in the cache LRU instead of the free
list.

The device never sees any of this: it gets a dense (n_slots, W) page table,
per-slot lengths, and last tokens. Inactive slots carry length 0 and a
scratch-zeroed page-table row, so their (masked, unused) lanes stay
shape-static in the jitted steps.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.serving.kv_pool import PageAllocator, SCRATCH_PAGE
from repro.serving.prefix_cache import PrefixCache, page_hashes
from repro.serving.telemetry import clock


@dataclasses.dataclass
class Request:
    """One in-flight request. The stop condition (budget + eos) is owned by
    the engine as a cot.StopPolicy; budget here is bookkeeping only."""
    rid: int
    prompt: List[int]               # directive token already appended
    mode: str
    budget: int
    out: List[int] = dataclasses.field(default_factory=list)
    preemptions: int = 0
    # times on telemetry.clock, read by the engine's recorder
    t_submit: float = 0.0
    t_admit: Optional[float] = None      # first admission
    t_first: Optional[float] = None      # first token (kept across preemption)
    t_done: Optional[float] = None
    n_admits: int = 0
    prefill_steps: int = 0               # steps that wrote part of the prompt


class PagedScheduler:
    def __init__(self, *, n_slots: int, n_pages: int, page_size: int,
                 max_pages_per_seq: int, prefix_cache: bool = False):
        self.n_slots = n_slots
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        self.alloc = PageAllocator(n_pages)
        self.cache: Optional[PrefixCache] = \
            PrefixCache(self.alloc) if prefix_cache else None
        self.page_table = np.full((n_slots, max_pages_per_seq), SCRATCH_PAGE,
                                  np.int32)
        self.lengths = np.zeros(n_slots, np.int32)      # tokens in cache
        self.prefill_progress = np.zeros(n_slots, np.int32)  # prompt written
        self.seq_pages: List[List[int]] = [[] for _ in range(n_slots)]
        self.active: Dict[int, Request] = {}
        self.waiting: Deque[Request] = deque()
        self.free_slots: List[int] = list(range(n_slots - 1, -1, -1))
        self._admit_order: Dict[int, int] = {}          # slot -> seqno
        self._admit_seq = 0
        self._hashes: Dict[int, List[bytes]] = {}       # slot -> page hashes
        self.n_evictions = 0
        self.prefix_hit_tokens = 0       # prompt tokens served from cache
        self.prefix_prompt_tokens = 0    # prompt tokens through admission

    # -- queue ---------------------------------------------------------------

    def submit(self, req: Request) -> None:
        need = -(-len(req.prompt) // self.page_size)
        if need > self.max_pages_per_seq:
            raise ValueError(
                f"prompt needs {need} pages > max_pages_per_seq "
                f"{self.max_pages_per_seq}")
        self.waiting.append(req)

    @property
    def idle(self) -> bool:
        return not self.active and not self.waiting

    # -- allocation ----------------------------------------------------------

    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """All-or-nothing alloc with second-chance eviction: when the free
        list is short, cold unreferenced prefix-cache pages are evicted
        (LRU first) before giving up — callers preempt only after this
        returns None."""
        pages = self.alloc.alloc(n)
        if pages is None and self.cache is not None:
            self.cache.evict(n - self.alloc.n_free)
            pages = self.alloc.alloc(n)
        return pages

    # -- admission -----------------------------------------------------------

    def admit(self, max_prefill_pages: int) -> List[Tuple[int, Request]]:
        """Admit FIFO-head requests while a slot + enough pages are free.

        The longest cached prefix of full prompt pages (if any) maps
        directly into the page table with a refcount each, then the request
        needs only its first uncached chunk's pages — min(tail pages,
        max_prefill_pages) — and enters with prefill_progress at the hit
        length; later chunks grow the page list via grow_to. At least one
        prompt token is always left to recompute so the mixed step produces
        last-token logits."""
        admitted = []
        while self.waiting and self.free_slots:
            req = self.waiting[0]
            total = -(-len(req.prompt) // self.page_size)
            hashes: List[bytes] = []
            hits: List[int] = []
            if self.cache is not None:
                hashes = page_hashes(req.prompt, self.page_size)
                hits = self.cache.lookup(hashes)
                if len(hits) * self.page_size >= len(req.prompt):
                    hits = hits[:-1]
            n_hit = len(hits)
            need = min(total - n_hit, max_prefill_pages)
            if hits:
                # reference the hits before allocating the tail, so tail
                # eviction can never reclaim them out from under us
                self.cache.acquire(hits)
            pages = self._alloc_pages(need)
            if pages is None:
                if hits:
                    self.alloc.free(hits)       # back to live/LRU state
                break
            self.waiting.popleft()
            slot = self.free_slots.pop()
            self.seq_pages[slot] = hits + pages
            self.page_table[slot, :] = SCRATCH_PAGE
            self.page_table[slot, :n_hit + need] = hits + pages
            hit_tokens = n_hit * self.page_size
            self.lengths[slot] = hit_tokens
            self.prefill_progress[slot] = hit_tokens
            if self.cache is not None:
                self._hashes[slot] = hashes
                self.prefix_hit_tokens += hit_tokens
                self.prefix_prompt_tokens += len(req.prompt)
            self.active[slot] = req
            if req.t_admit is None:
                req.t_admit = clock()
            req.n_admits += 1
            self._admit_order[slot] = self._admit_seq
            self._admit_seq += 1
            admitted.append((slot, req))
        return admitted

    # -- slot phases ---------------------------------------------------------

    def prefilling_slots(self) -> List[int]:
        """Active slots whose prompt is not fully written yet, in admission
        order (FIFO fairness for chunk scheduling)."""
        slots = [s for s in self.active
                 if self.prefill_progress[s] < len(self.active[s].prompt)]
        return sorted(slots, key=lambda s: self._admit_order[s])

    def decoding_slots(self) -> List[int]:
        return sorted(s for s in self.active
                      if self.prefill_progress[s] >= len(self.active[s].prompt))

    # -- page capacity --------------------------------------------------------

    def grow_to(self, slot: int, n_tokens: int) -> List[Request]:
        """Grow `slot`'s page list to cover `n_tokens` cache positions,
        evicting cold prefix-cache pages and then preempting the
        most-recently-admitted active request when the pool is dry —
        *including the grower itself*: a newest slot that can't
        grow yields (self-preempts) rather than starving older work, so the
        oldest request always makes monotonic progress and mutual-eviction
        livelock is impossible. Returns the preempted (requeued) requests —
        the caller must re-derive any slot sets it holds (and check the
        grower survived), since victims may be mid-prefill: their pages,
        including partially-written prompt pages, are freed and their
        progress reset (preemption-safe partial-prefill release)."""
        need_pages = -(-n_tokens // self.page_size)
        if need_pages > self.max_pages_per_seq:
            raise RuntimeError(
                f"sequence in slot {slot} exceeded max_pages_per_seq")
        evicted = []
        while need_pages > len(self.seq_pages[slot]):
            page = self._alloc_pages(1)
            if page is None:
                if len(self.active) <= 1:
                    raise RuntimeError(
                        "KV pool too small for a single sequence")
                victim = max(self.active, key=lambda s: self._admit_order[s])
                evicted.append(self._preempt(victim))
                if victim == slot:
                    return evicted
                continue
            pidx = len(self.seq_pages[slot])
            self.seq_pages[slot].append(page[0])
            self.page_table[slot, pidx] = page[0]
        return evicted

    def truncate_to(self, slot: int, n_tokens: int) -> None:
        """Shrink `slot`'s page list to exactly cover `n_tokens` cache
        positions, returning surplus pages grown for a speculative window
        whose tail was rejected. Surplus pages release through the same
        `_return_pages` choke point as preemption, so prefix-cache parked
        pages and refcounts stay consistent; shared prefix-hit pages are
        never surplus (the kept prefix always spans at least the prompt's
        cached pages — the engine only truncates back to a length >= the
        pre-speculation committed length)."""
        need = -(-n_tokens // self.page_size)
        pages = self.seq_pages[slot]
        if len(pages) <= need:
            return
        surplus = pages[need:]
        self.seq_pages[slot] = pages[:need]
        self.page_table[slot, need:len(pages)] = SCRATCH_PAGE
        self._return_pages(surplus)

    def _return_pages(self, pages: List[int]) -> None:
        """THE page-release choke point: every refcount decrement the
        scheduler performs funnels through here (completion and preemption
        both route via `_release`). A shared page only loses this holder;
        a cached page whose last holder leaves parks in the prefix-cache
        LRU instead of the free list."""
        self.alloc.free(pages)

    def _release(self, slot: int) -> Request:
        req = self.active.pop(slot)
        self._return_pages(self.seq_pages[slot])
        self.seq_pages[slot] = []
        self.page_table[slot, :] = SCRATCH_PAGE
        self.lengths[slot] = 0
        self.prefill_progress[slot] = 0
        self._admit_order.pop(slot, None)
        self._hashes.pop(slot, None)
        self.free_slots.append(slot)
        return req

    def _preempt(self, slot: int) -> Request:
        req = self._release(slot)
        req.out = []                 # recompute preemption: restart cleanly
        req.preemptions += 1
        self.n_evictions += 1
        self.waiting.appendleft(req)
        return req

    # -- completion ----------------------------------------------------------

    def complete(self, slot: int) -> Request:
        """Finish a request: promote its *full* prompt pages into the
        prefix cache (immutable by now — the partial tail page and decode
        writes land strictly after them), then release the slot."""
        if self.cache is not None:
            req = self.active[slot]
            n_full = len(req.prompt) // self.page_size
            hashes = self._hashes.get(slot)
            if hashes is None:
                hashes = page_hashes(req.prompt, self.page_size)
            self.cache.insert(hashes[:n_full],
                              self.seq_pages[slot][:n_full])
        return self._release(slot)
