"""Continuous-batching scheduler unit tests: admission, completion, page
reclaim, preemption, and no cross-sequence leakage through the shared pool."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from conftest import dense_greedy
from repro.configs import get_arch, reduced
from repro.models import transformer
from repro.serving import ContinuousBatchingEngine
from repro.serving.kv_pool import SCRATCH_PAGE, PageAllocator
from repro.serving.scheduler import PagedScheduler, Request


def mk_req(rid, n, budget=4):
    return Request(rid=rid, prompt=list(range(1, n + 1)), mode="slow_think",
                   budget=budget)


def test_allocator_free_list_reuse():
    a = PageAllocator(6)                      # pages 1..5 allocatable
    got = a.alloc(5)
    assert sorted(got) == [1, 2, 3, 4, 5] and a.alloc(1) is None
    a.free(got[:2])
    assert sorted(a.alloc(2)) == sorted(got[:2])
    with pytest.raises(AssertionError):
        a.free([SCRATCH_PAGE])


def test_allocator_rejects_double_free_and_bad_ids():
    """The two pool-corrupting bugs fail fast: freeing a page twice (it
    would re-enter the free list while a live sequence still maps it) and
    freeing an id outside 1..n_pages-1 (a stale page-table row)."""
    a = PageAllocator(6)
    got = a.alloc(3)
    a.free(got[:1])
    with pytest.raises(AssertionError, match="double free"):
        a.free(got[:1])                       # already back in the list
    with pytest.raises(AssertionError, match="double free"):
        a.free(got[1:2] + got[1:2])           # twice in one call
    # state stays consistent: pages 1 and 2 went back, 3 is still out
    assert a.n_free == 4 and sorted(a.alloc(4)) == [1, 2, 4, 5]
    with pytest.raises(AssertionError, match="out of range"):
        a.free([6])
    with pytest.raises(AssertionError, match="out of range"):
        a.free([-1])
    with pytest.raises(AssertionError):
        a.free([SCRATCH_PAGE])


def test_admission_respects_slots_and_pages():
    s = PagedScheduler(n_slots=2, n_pages=5, page_size=4, max_pages_per_seq=4)
    for rid, n in enumerate([8, 4, 4]):       # 2, 1, 1 pages
        s.submit(mk_req(rid, n))
    admitted = s.admit(max_prefill_pages=4)   # whole prompts
    # slots bound admission to 2 even though pages remain for the third
    assert [r.rid for _, r in admitted] == [0, 1]
    assert s.alloc.n_free == 1 and len(s.waiting) == 1
    # page table rows populated, scratch elsewhere
    for slot, req in admitted:
        need = -(-len(req.prompt) // 4)
        assert (s.page_table[slot, :need] != SCRATCH_PAGE).all()
        assert (s.page_table[slot, need:] == SCRATCH_PAGE).all()


def test_completion_reclaims_pages_and_slot():
    s = PagedScheduler(n_slots=1, n_pages=4, page_size=4, max_pages_per_seq=3)
    s.submit(mk_req(0, 12))                   # all 3 pages
    [(slot, _)] = s.admit(max_prefill_pages=3)
    assert s.alloc.n_free == 0 and not s.admit(max_prefill_pages=3)
    s.submit(mk_req(1, 12))
    s.complete(slot)
    assert s.alloc.n_free == 3
    assert (s.page_table[slot] == SCRATCH_PAGE).all() and s.lengths[slot] == 0
    # freed pages admit the waiting request immediately
    assert [r.rid for _, r in s.admit(max_prefill_pages=3)] == [1]


def test_decode_capacity_growth_and_preemption():
    s = PagedScheduler(n_slots=2, n_pages=4, page_size=4, max_pages_per_seq=3)
    s.submit(mk_req(0, 3))
    s.submit(mk_req(1, 3))
    s.admit(max_prefill_pages=3)              # one page each, one free
    # seq 0 crosses a page boundary -> grows from the free list
    s.lengths[0] = 4
    assert s.grow_to(0, s.lengths[0] + 1) == []
    assert len(s.seq_pages[0]) == 2 and s.alloc.n_free == 0
    # seq 1 crosses next: pool dry -> the *newest* active is preempted, and
    # seq 1 is itself the newest: it yields instead of starving seq 0
    s.lengths[1] = 4
    evicted = s.grow_to(1, s.lengths[1] + 1)
    assert [r.rid for r in evicted] == [1]
    assert evicted[0].out == [] and evicted[0].preemptions == 1
    assert s.waiting[0].rid == 1              # requeued at the front
    assert len(s.seq_pages[0]) == 2 and 0 in s.active and 1 not in s.active
    # with seq 1 gone its pages are free again: a re-admitted seq 1 whose
    # growth hits a dry pool is now the victim of choice for seq 0
    [(slot1, _)] = s.admit(max_prefill_pages=3)
    s.lengths[slot1] = 2
    s.lengths[0] = 8                          # needs a third page
    evicted = s.grow_to(0, s.lengths[0] + 1)
    assert [r.rid for r in evicted] == [1]    # oldest keeps progressing
    assert len(s.seq_pages[0]) == 3


def test_no_cross_sequence_leakage():
    """Concurrent requests through the shared pool generate exactly what
    they generate alone — pages can't bleed across sequences, including
    after completion frees pages mid-flight for reuse."""
    cfg = reduced(get_arch("pangu_1b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[7, 8, 9], list(range(1, 18)), [4] * 9, [11, 3, 5, 2]]
    budgets = [3, 12, 6, 9]                   # staggered completions

    solo = []
    for p, n in zip(prompts, budgets):
        eng = ContinuousBatchingEngine(params, cfg, kv_bits=16, page_size=8,
                                       max_batch=1, max_seq_len=64)
        solo.append(eng.run([p], max_new=n).tokens[0])

    eng = ContinuousBatchingEngine(params, cfg, kv_bits=16, page_size=8,
                                   max_batch=4, max_seq_len=64)
    for p, n in zip(prompts, budgets):
        eng.submit(p, max_new=n)
    while not eng.sched.idle:
        eng.step()
    together = [eng._requests[r].out for r in range(4)]
    assert together == solo


def test_continuous_matches_legacy_engine():
    """The paged engine (fp16 pool) reproduces the plain dense greedy loop
    on a second architecture (qk-norm, tied embeddings)."""
    cfg = reduced(get_arch("qwen3_0_6b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 6, 7], list(range(1, 20)), [9] * 11]
    eng = ContinuousBatchingEngine(params, cfg, kv_bits=16, page_size=8,
                                   max_batch=3, max_seq_len=64)
    res = eng.run(prompts, mode="no_think", max_new=6)
    assert res.tokens == dense_greedy(params, cfg, prompts, mode="no_think",
                                      max_new=6)


def test_preemption_preserves_outputs():
    """A pool too small for all sequences at once: requests are evicted and
    recomputed, but every request still finishes with the same tokens."""
    cfg = reduced(get_arch("pangu_1b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 6, 7], list(range(1, 20)), [9] * 11, [3, 1, 4, 1, 5]]

    roomy = ContinuousBatchingEngine(params, cfg, kv_bits=8, page_size=8,
                                     max_batch=4, max_seq_len=64)
    want = roomy.run(prompts, max_new=8).tokens
    tight = ContinuousBatchingEngine(params, cfg, kv_bits=8, page_size=8,
                                     max_batch=4, max_seq_len=64, n_pages=9)
    res = tight.run(prompts, max_new=8)
    assert res.evictions > 0
    assert res.tokens == want


def test_int8_pool_close_to_fp16_pool():
    """Paged int8 KV decode stays close to the fp16-pool decode (and the
    pool really is ~half the bytes)."""
    cfg = reduced(get_arch("pangu_1b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [list(range(1, 14)), [8] * 6]
    engines = {}
    for kv_bits in (16, 8):
        engines[kv_bits] = ContinuousBatchingEngine(
            params, cfg, kv_bits=kv_bits, page_size=8, max_batch=2,
            max_seq_len=64)
    r16 = engines[16].run(prompts, max_new=10)
    r8 = engines[8].run(prompts, max_new=10)
    agree = np.mean([a == b for t16, t8 in zip(r16.tokens, r8.tokens)
                     for a, b in zip(t16, t8)])
    assert agree >= 0.5, agree
    ratio = engines[8].kv_bytes_per_token() / engines[16].kv_bytes_per_token()
    assert ratio <= 0.55, ratio


def test_int4_pool_bytes_under_three_tenths_of_fp16():
    """Packed-int4 pages (two codes a byte along head_dim, one scale per
    page and head) hold a token in at most 30% of the fp16 pool's bytes."""
    cfg = reduced(get_arch("pangu_1b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    bpt = {kv_bits: ContinuousBatchingEngine(
               params, cfg, kv_bits=kv_bits, page_size=16, max_batch=1,
               max_seq_len=64).kv_bytes_per_token()
           for kv_bits in (16, 4)}
    assert bpt[4] / bpt[16] <= 0.30, bpt
