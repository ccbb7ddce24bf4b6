"""Refcounted prefix caching: allocator sharing, chained page hashes, LRU
eviction, scheduler admission hits, shared-page preemption, and engine-level
bit-exactness of cache hits vs recompute (bf16, int8, and packed-int4
pools)."""
import jax
import numpy as np
import pytest
from conftest import QUANT_KV_BITS, make_engine, pool_leaves

from repro.serving.kv_pool import SCRATCH_PAGE, PageAllocator
from repro.serving.prefix_cache import PrefixCache, page_hashes
from repro.serving.scheduler import PagedScheduler, Request


def mk_req(rid, prompt, budget=4):
    return Request(rid=rid, prompt=list(prompt), mode="slow_think",
                   budget=budget)


# ---------------------------------------------------------------------------
# allocator refcounts
# ---------------------------------------------------------------------------

def test_allocator_refcount_sharing():
    a = PageAllocator(6)
    got = a.alloc(2)
    a.incref(got[0])
    assert a.refcount(got[0]) == 2 and a.refcount(got[1]) == 1
    a.free(got)                               # one holder of each
    assert a.refcount(got[0]) == 1 and a.n_free == 4
    with pytest.raises(AssertionError, match="double free"):
        a.free(got[1:])                       # refcount already 0
    a.free(got[:1])                           # last holder
    assert a.n_free == 5 and a.n_live == 0
    with pytest.raises(AssertionError, match="incref"):
        a.incref(got[0])                      # can't share a freed page


def test_allocator_park_adopt_reclaim():
    a = PageAllocator(6)
    claimed = []
    a.reclaim_hook = lambda p: claimed.append(p) or p % 2 == 1
    got = a.alloc(5)
    a.free(got)
    parked = [p for p in got if p % 2 == 1]
    assert sorted(claimed) == sorted(got)
    assert a.n_parked == len(parked) and a.n_free == 5 - len(parked)
    a.adopt(parked[0])                        # cache hit on a cold page
    assert a.refcount(parked[0]) == 1 and a.n_parked == len(parked) - 1
    with pytest.raises(AssertionError, match="adopt"):
        a.adopt(parked[0])
    a.reclaim(parked[1])                      # cache eviction
    assert a.n_parked == len(parked) - 2
    a.free([parked[0]])
    # invariant across every transition
    assert a.n_free + a.n_live + a.n_parked == 5


# ---------------------------------------------------------------------------
# chained page hashes
# ---------------------------------------------------------------------------

def test_page_hashes_chain_position_and_content():
    toks = list(range(10, 30))
    hs = page_hashes(toks, 8)
    assert len(hs) == 2                       # 20 tokens -> 2 full pages
    # shared prefix -> shared hash prefix; a divergence poisons the chain
    other = toks[:8] + [99] + toks[9:]
    ho = page_hashes(other, 8)
    assert ho[0] == hs[0] and ho[1] != hs[1]
    # same page content at a different position hashes differently
    assert page_hashes(toks[8:], 8)[0] != hs[1]
    # partial trailing pages are never hashed
    assert page_hashes(toks[:7], 8) == []


# ---------------------------------------------------------------------------
# PrefixCache LRU
# ---------------------------------------------------------------------------

def test_prefix_cache_lru_eviction_order():
    a = PageAllocator(8)
    cache = PrefixCache(a)
    hs = page_hashes(list(range(12)), 4)      # 3 hashes
    pages = a.alloc(3)
    assert cache.insert(hs, pages) == 3
    a.free(pages)                             # all park, LRU order = pages
    assert cache.n_unreferenced == 3 and a.n_parked == 3
    cache.acquire(pages[:1])                  # page 0 adopted -> referenced
    assert cache.n_unreferenced == 2
    a.free(pages[:1])                         # re-parks at the MRU end
    assert cache.evict(1) == 1                # coldest first: pages[1]
    assert cache.n_cached == 2 and a.n_free == 5
    assert cache.lookup(hs) == pages[:1]      # gap at hs[1] ends the run
    assert cache.evict(5) == 2                # drains, never over-frees
    assert cache.n_cached == 0 and a.n_free == 7 and cache.n_evicted == 3


# ---------------------------------------------------------------------------
# scheduler admission
# ---------------------------------------------------------------------------

def _finish_prefill(s, slot):
    n = len(s.active[slot].prompt)
    s.prefill_progress[slot] = n
    s.lengths[slot] = n


def test_admission_maps_cached_prefix():
    s = PagedScheduler(n_slots=2, n_pages=12, page_size=4,
                       max_pages_per_seq=4, prefix_cache=True)
    prompt = list(range(100, 110))            # 2 full pages + 2 tail tokens
    s.submit(mk_req(0, prompt))
    [(slot, _)] = s.admit(max_prefill_pages=4)
    assert s.prefill_progress[slot] == 0      # cold: nothing cached yet
    shared = s.seq_pages[slot][:2]
    _finish_prefill(s, slot)
    s.complete(slot)                          # promotes the 2 full pages
    assert s.cache.n_cached == 2 and s.cache.n_unreferenced == 2

    s.submit(mk_req(1, prompt))
    [(slot2, _)] = s.admit(max_prefill_pages=4)
    assert s.seq_pages[slot2][:2] == shared   # mapped, not reallocated
    assert int(s.prefill_progress[slot2]) == 8 == int(s.lengths[slot2])
    assert list(s.page_table[slot2, :3]) == s.seq_pages[slot2]
    assert s.prefix_hit_tokens == 8
    assert s.prefix_prompt_tokens == 2 * len(prompt)
    assert s.alloc.refcount(shared[0]) == 1   # adopted out of the LRU
    assert s.cache.n_unreferenced == 0


def test_page_aligned_prompt_recomputes_last_page():
    """A fully-cached page-aligned prompt must still recompute >= 1 token,
    else the mixed step has no last-token logits to sample from."""
    s = PagedScheduler(n_slots=2, n_pages=12, page_size=4,
                       max_pages_per_seq=4, prefix_cache=True)
    prompt = list(range(200, 208))            # exactly 2 pages
    s.submit(mk_req(0, prompt))
    [(slot, _)] = s.admit(max_prefill_pages=4)
    _finish_prefill(s, slot)
    s.complete(slot)
    assert s.cache.n_cached == 2
    s.submit(mk_req(1, prompt))
    [(slot2, _)] = s.admit(max_prefill_pages=4)
    # only the first page hits; the whole last page is recomputed
    assert int(s.prefill_progress[slot2]) == 4
    assert len(s.seq_pages[slot2]) == 2


def test_preempting_shared_holder_only_drops_refcount():
    s = PagedScheduler(n_slots=3, n_pages=16, page_size=4,
                       max_pages_per_seq=4, prefix_cache=True)
    prompt = list(range(50, 60))
    s.submit(mk_req(0, prompt))
    [(slot, _)] = s.admit(max_prefill_pages=4)
    _finish_prefill(s, slot)
    s.complete(slot)
    s.submit(mk_req(1, prompt))
    s.submit(mk_req(2, prompt))
    admitted = s.admit(max_prefill_pages=4)
    (sa, _), (sb, _) = admitted
    shared = s.seq_pages[sa][:2]
    assert s.seq_pages[sb][:2] == shared
    assert all(s.alloc.refcount(p) == 2 for p in shared)
    tail_a = s.seq_pages[sa][2]
    s._preempt(sb)                            # newest-yields victim
    # survivor's mapping is untouched; shared pages lost one holder only
    assert all(s.alloc.refcount(p) == 1 for p in shared)
    assert s.alloc.refcount(tail_a) == 1
    assert s.seq_pages[sa][:2] == shared
    assert list(s.page_table[sa, :3]) == s.seq_pages[sa]
    assert (s.page_table[sb] == SCRATCH_PAGE).all()


def test_lru_eviction_precedes_preemption():
    """A dry free list drains the cache LRU before any active request is
    preempted — the second-chance free list."""
    s = PagedScheduler(n_slots=2, n_pages=4, page_size=4,
                       max_pages_per_seq=3, prefix_cache=True)
    s.submit(mk_req(0, list(range(30, 38))))  # 2 pages, both promotable
    [(slot, _)] = s.admit(max_prefill_pages=3)
    _finish_prefill(s, slot)
    s.complete(slot)
    assert s.cache.n_unreferenced == 2 and s.alloc.n_free == 1
    s.submit(mk_req(1, list(range(60, 72))))  # 3 pages, no hits
    [(slot2, _)] = s.admit(max_prefill_pages=3)
    assert len(s.seq_pages[slot2]) == 3       # evicted 2 cold pages to fit
    assert s.cache.n_evicted == 2 and s.cache.n_cached == 0
    assert s.n_evictions == 0                 # nobody was preempted


# ---------------------------------------------------------------------------
# engine: cache hits are bit-exact with recompute
# ---------------------------------------------------------------------------

def _shared_prompts(page=8):
    common = list(range(1, 4 * page + 1))     # 4 shared full pages
    return [common + [401, 402, 403],
            common + [404, 405, 406, 407, 408],
            common + list(range(409, 409 + page))]


def test_engine_cache_hits_bitexact(cfg_params, kv_bits):
    cfg, params = cfg_params
    prompts = _shared_prompts()
    want = make_engine(params, cfg, kv_bits=kv_bits).run(prompts, max_new=8)
    eng = make_engine(params, cfg, kv_bits=kv_bits, prefix_cache=True)
    cold = eng.run(prompts, max_new=8)
    warm = eng.run(prompts, max_new=8)
    assert cold.tokens == want.tokens         # cold pass: no hits, no drift
    assert warm.tokens == want.tokens         # warm pass: hits, bit-exact
    assert cold.prefix_hit_tokens == 0
    assert warm.prefix_hit_tokens >= 3 * 4 * 8    # every shared page hit
    assert eng.compile_counts() == {"mixed": 1, "decode": 1, "verify": 0}
    stats = eng.prefix_cache_stats()
    assert stats["hit_rate"] > 0.4 and stats["cached_pages"] > 0


@pytest.mark.parametrize("kv_bits", QUANT_KV_BITS)
def test_warm_hits_reuse_identical_quantized_pages(cfg_params, kv_bits):
    """The pages a warm request maps are the exact quantized codes + scales
    the cold request wrote — int8 bytes and packed int4 nibbles alike are
    never requantized or rewritten on a hit."""
    cfg, params = cfg_params
    prompts = _shared_prompts()
    eng = make_engine(params, cfg, kv_bits=kv_bits, prefix_cache=True)
    eng.run(prompts, max_new=8)
    cached = sorted(eng.sched.cache._by_hash.values())
    assert cached
    before = jax.device_get(eng.pools)
    warm = eng.run(prompts, max_new=8)
    after = jax.device_get(eng.pools)
    assert warm.prefix_hit_tokens > 0
    for blk in before:
        for name in pool_leaves(kv_bits):
            np.testing.assert_array_equal(
                before[blk][name][:, cached], after[blk][name][:, cached])


def test_mid_prefill_preemption_with_shared_pages(cfg_params, kv_bits):
    """Tight pool + shared prefixes: preempting holders of shared pages
    (refcount drops, no double-free) and evicting cold cached pages still
    reproduces the roomy cache-off engine token-for-token."""
    cfg, params = cfg_params
    prompts = _shared_prompts()
    roomy = make_engine(params, cfg, kv_bits=kv_bits)
    want = roomy.run(prompts, max_new=8)
    tight = make_engine(params, cfg, kv_bits=kv_bits, n_pages=13,
                        prefix_cache=True)
    got = tight.run(prompts, max_new=8)
    assert got.tokens == want.tokens
    assert got.evictions > 0                  # preemption actually happened
    # and a second pass over the survivor cache still matches
    assert tight.run(prompts, max_new=8).tokens == want.tokens
