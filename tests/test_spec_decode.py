"""Draft-free self-speculative decoding: n-gram drafter lookup, verify-step
equivalence with sequential decode, page-exact rollback (kv_pool.truncate),
and engine-level guarantees — bf16 greedy bit-exactness vs vanilla decode
(including under preemption), int8 smoke + counter consistency, budget stops
mid-window, and the one-extra-program compile-count bound."""
import types

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import QUANT_KV_BITS, make_engine

from repro.models import transformer
from repro.serving import ContinuousBatchingEngine
from repro.serving.draft import NgramDrafter
from repro.serving import kv_pool


# ---------------------------------------------------------------------------
# NgramDrafter
# ---------------------------------------------------------------------------

def test_drafter_copies_continuation_of_trailing_ngram():
    d = NgramDrafter(5, ngram_max=3, ngram_min=2)
    # trailing [1,2,3] recurs at the start; lag 5 -> copy x[t-5] forward
    assert d.propose([1, 2, 3, 4, 5, 1, 2, 3]) == [4, 5, 1, 2, 3]


def test_drafter_prefers_most_recent_occurrence():
    d = NgramDrafter(1, ngram_max=3, ngram_min=2)
    # [1,2,3] occurs twice with different continuations (7 then 8): the
    # match closest to the end wins
    assert d.propose([9, 1, 2, 3, 7, 1, 2, 3, 8, 1, 2, 3]) == [8]


def test_drafter_prefers_longest_ngram():
    d = NgramDrafter(1, ngram_max=3, ngram_min=2)
    # 2-gram [2,3] recurs most recently before 9, but the 3-gram [1,2,3]
    # also recurs (before 7) and is tried first
    assert d.propose([1, 2, 3, 7, 5, 2, 3, 9, 1, 2, 3]) == [7]


def test_drafter_lag_recurrence_rolls_into_drafts():
    d = NgramDrafter(6, ngram_max=3, ngram_min=2)
    # period-2 loop: the copy source runs off the context's end and reads
    # the drafts themselves, still yielding all k tokens
    assert d.propose([4, 7, 4, 7, 4, 7]) == [4, 7, 4, 7, 4, 7]


def test_drafter_empty_on_fresh_context():
    d = NgramDrafter(4, ngram_max=3, ngram_min=2)
    assert d.propose(list(range(20))) == []
    assert d.propose([3]) == []                   # too short to have a bigram


def test_drafter_ngram_min_blocks_single_token_matches():
    ctx = [3, 1, 4, 1]                            # only the 1-gram [1] recurs
    assert NgramDrafter(4, ngram_max=3, ngram_min=2).propose(ctx) == []
    assert NgramDrafter(4, ngram_max=3, ngram_min=1).propose(ctx) == \
        [4, 1, 4, 1]


def test_drafter_k_clamps():
    d = NgramDrafter(8, ngram_max=3, ngram_min=2)
    ctx = [1, 2, 3, 4, 5, 1, 2, 3]
    assert d.propose(ctx, k=2) == [4, 5]
    assert d.propose(ctx, k=0) == []


# ---------------------------------------------------------------------------
# kv_pool.truncate: rollback is bit-identical to never having speculated
# ---------------------------------------------------------------------------

def test_truncate_bit_identical_to_direct_write(kv_bits):
    cfg = types.SimpleNamespace(n_kv_heads=2, hd=4)
    page, c = 4, 5                                # k+1 window, unaligned
    pool0 = kv_pool.init_pool(cfg, n_pages=8, page_size=page,
                              kv_bits=kv_bits)
    rng = np.random.default_rng(0)
    rows = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    # pre-existing history so the boundary page holds old tokens
    hist = [jnp.asarray(rng.normal(size=(2, c, 2, 4)), jnp.float32)
            for _ in range(2)]
    start = jnp.asarray([3, 1], jnp.int32)
    pool0 = kv_pool.write_chunk(pool0, hist[0], hist[1], rows,
                                jnp.zeros(2, jnp.int32), start)
    k = jnp.asarray(rng.normal(size=(2, c, 2, 4)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, c, 2, 4)), jnp.float32)
    n_keep = jnp.asarray([2, 4], jnp.int32)

    snap = {leaf: pool0[leaf][rows] for leaf in pool0}
    full = kv_pool.write_chunk(pool0, k, v, rows, start,
                               jnp.full(2, c, jnp.int32))
    rolled = kv_pool.truncate(full, rows, snap, k, v, start, n_keep)
    direct = kv_pool.write_chunk(pool0, k, v, rows, start, n_keep)
    for leaf in pool0:
        np.testing.assert_array_equal(np.asarray(rolled[leaf]),
                                      np.asarray(direct[leaf]))


# ---------------------------------------------------------------------------
# verify_step_paged == sequential decode_step_paged (bf16 pools)
# ---------------------------------------------------------------------------

def test_verify_window_matches_sequential_decode(cfg_params):
    """Scoring a k+1 window in one verify pass reproduces the logits the
    vanilla chain produces token-by-token (bf16: the raw-window splice is
    exactly what decode would have written; int8 deviates by design —
    covered at engine level)."""
    cfg, params = cfg_params
    pools = transformer.init_paged_pools(cfg, n_pages=8, page_size=8,
                                         kv_bits=16)
    pt = jnp.asarray([[1, 2, 3]], jnp.int32)
    toks = list(np.random.default_rng(1).integers(0, cfg.vocab, 8))

    ref, pv = [], pools
    for i, t in enumerate(toks):
        lg, pv = transformer.decode_step_paged(
            params, pv, pt, jnp.asarray([t], jnp.int32),
            jnp.asarray([i], jnp.int32), cfg)
        ref.append(np.asarray(lg[0]))

    pw = pools
    for i, t in enumerate(toks[:4]):              # shared history
        _, pw = transformer.decode_step_paged(
            params, pw, pt, jnp.asarray([t], jnp.int32),
            jnp.asarray([i], jnp.int32), cfg)
    win, _ = transformer.verify_step_paged(
        params, pw, pt, jnp.asarray([toks[4:]], jnp.int32),
        jnp.asarray([4], jnp.int32), jnp.asarray([4], jnp.int32), cfg)
    win = np.asarray(win[0])                      # (4, V)
    for j in range(4):
        assert int(win[j].argmax()) == int(ref[4 + j].argmax())
        np.testing.assert_allclose(win[j], ref[4 + j], rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# engine: bf16 greedy speculation is bit-exact with vanilla decode
# ---------------------------------------------------------------------------

def _loopy_prompts():
    # short prompts whose reduced-model greedy continuations loop quickly,
    # so the drafter actually fires (same family the bench warmup uses)
    return [[7] * 8 + list(range(16)),
            [5] * 12 + [1, 2, 3, 4],
            [9, 9, 9, 9] + list(range(30, 42))]


MK = dict(page_size=8, max_batch=3, max_seq_len=96)


def test_engine_spec_bf16_greedy_bit_exact(cfg_params):
    cfg, params = cfg_params
    prompts = _loopy_prompts()
    want = ContinuousBatchingEngine(params, cfg, kv_bits=16, **MK).run(
        prompts, max_new=32)
    eng = ContinuousBatchingEngine(params, cfg, kv_bits=16, spec_decode=4,
                                   spec_gate=0.5, **MK)
    got = eng.run(prompts, max_new=32)
    assert got.tokens == want.tokens
    assert got.spec_steps > 0                     # speculation actually ran
    assert got.accepted_tokens > 0
    st = eng.spec_stats()
    assert st["accepted_tokens"] <= st["draft_tokens"]
    assert 0.0 <= st["acceptance_rate"] <= 1.0
    assert eng.compile_counts() == {"mixed": 1, "decode": 1, "verify": 1}


def test_engine_spec_bit_exact_under_preemption(cfg_params):
    """A tight pool preempts mid-speculation: rollback + requeue must still
    reproduce the roomy vanilla engine token-for-token."""
    cfg, params = cfg_params
    prompts = _loopy_prompts()
    want = ContinuousBatchingEngine(params, cfg, kv_bits=16, **MK).run(
        prompts, max_new=24)
    tight = ContinuousBatchingEngine(params, cfg, kv_bits=16, spec_decode=4,
                                     spec_gate=0.5, n_pages=16, **MK)
    got = tight.run(prompts, max_new=24)
    assert got.tokens == want.tokens
    assert got.evictions > 0                      # preemption happened


def test_engine_spec_budget_stops_mid_window(cfg_params):
    """max_new smaller than the k+1 window: accepted tokens past the budget
    must be dropped, not emitted."""
    cfg, params = cfg_params
    prompts = _loopy_prompts()
    want = ContinuousBatchingEngine(params, cfg, kv_bits=16, **MK).run(
        prompts, max_new=5)
    eng = ContinuousBatchingEngine(params, cfg, kv_bits=16, spec_decode=4,
                                   spec_gate=0.5, **MK)
    got = eng.run(prompts, max_new=5)
    assert got.tokens == want.tokens
    assert all(len(t) <= 5 for t in got.tokens)


@pytest.mark.parametrize("kv_bits", QUANT_KV_BITS)
def test_engine_spec_quantized_smoke(cfg_params, kv_bits):
    """Quantized pools (int8 and packed int4) re-round pages write-by-write,
    so batched verify is not bit-exact with vanilla by design — the
    machinery must still produce valid tokens, consistent counters, and the
    same compile-count bound."""
    cfg, params = cfg_params
    eng = make_engine(params, cfg, kv_bits=kv_bits, spec_decode=4,
                      spec_gate=0.5, **MK)
    got = eng.run(_loopy_prompts(), max_new=32)
    assert all(len(t) <= 32 for t in got.tokens)
    assert all(0 <= tok < cfg.vocab for t in got.tokens for tok in t)
    assert got.draft_tokens >= got.accepted_tokens >= 0
    if got.spec_steps:
        assert eng.compile_counts()["verify"] == 1
    assert sum(eng.compile_counts().values()) <= 3

