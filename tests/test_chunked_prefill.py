"""Chunked-prefill regression tests: fused quantize-on-write page writes
(`kv_pool.write_chunk` vs the one-shot and per-token paths) across all pool
dtypes (bf16/int8/packed-int4), the chunk attention kernel (Pallas
interpret vs jnp oracle vs dense causal SDPA), one packed mixed step
against the full forward pass, and the chunked engine against the dense
forward, including preemption mid-prefill."""
from types import SimpleNamespace

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from conftest import (MIXED_LANES, dense_greedy, make_engine,
                      packed_mixed_step, pages_changed, pool_leaves)
from repro.kernels.paged_prefill import (paged_prefill_attention,
                                         paged_prefill_attention_ref)
from repro.models import attention as attn
from repro.models import transformer
from repro.serving import kv_pool


def _geom(nkv, hd):
    return SimpleNamespace(n_kv_heads=nkv, hd=hd)


def _pool_with_tables(b, n_seq_pages, page, nkv, hd, kv_bits):
    pool = kv_pool.init_pool(_geom(nkv, hd), 1 + b * n_seq_pages, page,
                             kv_bits=kv_bits)
    pt = np.arange(1, 1 + b * n_seq_pages, dtype=np.int32).reshape(
        b, n_seq_pages)
    return pool, jnp.asarray(pt)


# ---------------------------------------------------------------------------
# write_prefill edge cases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [16, 1, 5])   # exact page multiple, single, odd
def test_write_prefill_edge_cases(kv_bits, n):
    """Page-multiple prompts, a length-1 prompt, and scratch-padded bucket
    rows all round-trip: valid positions match, padding cannot leak into
    scales, scratch-row writes are zeros."""
    page, nkv, hd, b = 8, 2, 16, 1
    s = 4 * page                                   # bucket > needed pages
    rng = np.random.default_rng(n)
    k = rng.normal(size=(b, s, nkv, hd)).astype(np.float32)
    k[:, n:] = 37.0                                # garbage beyond length
    pool, _ = _pool_with_tables(b, 4, page, nkv, hd, kv_bits)
    need = -(-n // page)
    rows = np.full((b, 4), kv_pool.SCRATCH_PAGE, np.int32)
    rows[0, :need] = range(1, 1 + need)
    pool = kv_pool.write_prefill(pool, jnp.asarray(k), jnp.asarray(k),
                                 jnp.asarray(rows),
                                 jnp.full((b,), n, jnp.int32))
    full = jnp.asarray(np.arange(1, 5, dtype=np.int32)[None, :])
    kc, _ = kv_pool.gather_kv(pool, full)
    got = np.asarray(kc, np.float32)
    am = float(np.abs(k[:, :n]).max())
    # quantized pools: one page-scale step of error (clip at the extremes)
    tol = {16: 0.02, 8: 2 * am / 255, 4: 2 * am / 15}[kv_bits]
    np.testing.assert_allclose(got[:, :n], k[:, :n], atol=tol)
    # positions past the length were zeroed before quantization: the 37s
    # can't inflate the page scale or survive in the pool
    if n < need * page:
        assert np.abs(got[:, n:need * page]).max() == 0.0
    # pages beyond the allocation were never written (rows were scratch)
    assert np.abs(got[:, need * page:]).max() == 0.0


# ---------------------------------------------------------------------------
# write_chunk vs the one-shot and per-token write paths
# ---------------------------------------------------------------------------

def test_write_chunk_matches_write_prefill(kv_bits):
    """Page-aligned chunks of a prompt land bit-identical to the one-shot
    write_prefill scatter — same quantized codes (int8 bytes or packed int4
    nibbles) *and* same per-(page, head) scales (fused quantize-on-write is
    not an approximation of the one-shot two-pass path on fresh pages)."""
    page, nkv, hd, b, n = 8, 2, 16, 2, 40          # 5 pages
    c = 2 * page                                   # chunk = 2 pages
    wc = kv_pool.chunk_window_pages(c, page)
    rng = np.random.default_rng(0)
    k = rng.normal(size=(b, n, nkv, hd)).astype(np.float32)
    v = rng.normal(size=(b, n, nkv, hd)).astype(np.float32)

    ref_pool, pt = _pool_with_tables(b, 5, page, nkv, hd, kv_bits)
    ref_pool = kv_pool.write_prefill(ref_pool, jnp.asarray(k), jnp.asarray(v),
                                     pt, jnp.full((b,), n, jnp.int32))

    got_pool, _ = _pool_with_tables(b, 5, page, nkv, hd, kv_bits)
    pt_np = np.asarray(pt)
    for start in range(0, n, c):
        n_new = min(c, n - start)
        chunk_k = np.zeros((b, c, nkv, hd), np.float32) + 99.0  # garbage tail
        chunk_v = np.zeros((b, c, nkv, hd), np.float32) + 99.0
        chunk_k[:, :n_new] = k[:, start:start + n_new]
        chunk_v[:, :n_new] = v[:, start:start + n_new]
        pidx0 = start // page
        rows = np.full((b, wc), kv_pool.SCRATCH_PAGE, np.int32)
        take = min(wc, 5 - pidx0)
        rows[:, :take] = pt_np[:, pidx0:pidx0 + take]
        got_pool = kv_pool.write_chunk(
            got_pool, jnp.asarray(chunk_k), jnp.asarray(chunk_v),
            jnp.asarray(rows), jnp.full((b,), start, jnp.int32),
            jnp.full((b,), n_new, jnp.int32))

    for name in pool_leaves(kv_bits):
        np.testing.assert_array_equal(
            np.asarray(got_pool[name][1:]), np.asarray(ref_pool[name][1:]),
            err_msg=name)


@pytest.mark.parametrize("kv_bits", [8, 4])
def test_write_chunk_decode_matches_write_token(kv_bits):
    """A riding decode slot (n_new=1 at an unaligned position) through
    write_chunk is bit-identical to the dedicated write_token path: same
    dequant (unpack for int4) -> mask -> merge -> requant semantics."""
    page, nkv, hd, b = 8, 2, 16, 2
    c = page                                       # 1-page chunks, wc = 2
    wc = kv_pool.chunk_window_pages(c, page)
    tok_pool, pt = _pool_with_tables(b, 2, page, nkv, hd, kv_bits)
    chk_pool = {k_: v_ for k_, v_ in tok_pool.items()}
    pt_np = np.asarray(pt)
    for pos in range(12):                          # crosses a page boundary
        k = np.asarray(jax.random.normal(jax.random.PRNGKey(pos),
                                         (b, nkv, hd))) * (1.0 + pos)
        kj = jnp.asarray(k)
        tok_pool = kv_pool.write_token(
            tok_pool, pt, jnp.full((b,), pos, jnp.int32), kj, kj)
        chunk = jnp.zeros((b, c, nkv, hd)).at[:, 0].set(kj) + 0.0
        pidx0 = pos // page
        rows = np.full((b, wc), kv_pool.SCRATCH_PAGE, np.int32)
        take = min(wc, 2 - pidx0)
        rows[:, :take] = pt_np[:, pidx0:pidx0 + take]
        chk_pool = kv_pool.write_chunk(
            chk_pool, chunk, chunk, jnp.asarray(rows),
            jnp.full((b,), pos, jnp.int32), jnp.ones((b,), jnp.int32))
    for name in ("k", "v", "k_s", "v_s"):
        np.testing.assert_array_equal(
            np.asarray(chk_pool[name][1:3]), np.asarray(tok_pool[name][1:3]),
            err_msg=name)


# ---------------------------------------------------------------------------
# chunk attention kernel: interpret vs oracle vs dense SDPA
# ---------------------------------------------------------------------------

# (q_start, n_new) lanes whose key counts q_start + n_new sit on the edges
# of the kernels' compute blocks (16-token pages, 16 pages a block, 36
# columns): one key, one page, a block, one page past a block, the whole
# table, a ragged count; chunk lanes beside decode lanes (n_new = 1).
BLOCK_EDGE_LANES = ((0, 1), (15, 1), (224, 32), (271, 1), (544, 32),
                    (269, 32))


@pytest.mark.parametrize("b,t,nq,nkv,hd,page,c,lanes", [
    pytest.param(2, 128, 4, 4, 64, 16, 32, None, id="2-128-4-4-64-16-32"),
    pytest.param(3, 96, 8, 2, 32, 16, 16, None, id="3-96-8-2-32-16-16"),
    pytest.param(1, 128, 4, 1, 64, 32, 32, None, id="1-128-4-1-64-32-32"),
    pytest.param(6, 576, 4, 2, 64, 16, 32, BLOCK_EDGE_LANES, id="blocks"),
])
def test_paged_prefill_kernel_matches_ref(b, t, nq, nkv, hd, page, c, lanes,
                                          kv_bits):
    """Chunk queries at staggered q_start against a long paged cache:
    Pallas interpret == jnp oracle, both within quantization tolerance of
    the dense causal SDPA over the original K/V. Cases: MHA, GQA 4x, MQA,
    and lanes on the edges of the walk's blocks."""
    rng = np.random.default_rng(3)
    k = rng.normal(size=(b, t, nkv, hd)).astype(np.float32)
    v = rng.normal(size=(b, t, nkv, hd)).astype(np.float32)
    n_seq_pages = t // page
    pool, pt = _pool_with_tables(b, n_seq_pages, page, nkv, hd, kv_bits)
    pool = kv_pool.write_prefill(pool, jnp.asarray(k), jnp.asarray(v), pt,
                                 jnp.full((b,), t, jnp.int32))
    q = jax.random.normal(jax.random.PRNGKey(1), (b, c, nq, hd), jnp.float32)
    # stagger chunk starts per sequence; mix full and partial (decode) lanes
    lanes = lanes or [((i * 24) % (t - c), c if i % 2 == 0 else 1)
                      for i in range(b)]
    q_start = jnp.asarray([q0 for q0, _ in lanes], jnp.int32)
    n_new = jnp.asarray([n for _, n in lanes], jnp.int32)
    kv_len = q_start + n_new

    ks, vs = pool.get("k_s"), pool.get("v_s")
    ref = paged_prefill_attention_ref(q, pool["k"], pool["v"], ks, vs, pt,
                                      q_start, kv_len)
    got = paged_prefill_attention(q, pool["k"], pool["v"], ks, vs, pt,
                                  q_start, kv_len, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # dense causal oracle over the original (unquantized) K/V
    kpos = jnp.arange(t)[None, None, :]
    qpos = (q_start[:, None] + jnp.arange(c)[None, :])[..., None]
    mask = ((kpos <= qpos) & (kpos < kv_len[:, None, None]))[:, None]
    dense = attn._sdpa(q, jnp.asarray(k), jnp.asarray(v),
                       mask.transpose(0, 1, 2, 3), None)
    # quant-noise tolerance grows ~(2^8-1)/(2^n-1) with narrower codes
    tol = {16: 0.03, 8: 0.12, 4: 0.75}[kv_bits]
    rows = np.asarray(n_new)[:, None] > np.arange(c)[None, :]  # valid rows
    d = np.abs(np.asarray(got).reshape(b, c, -1)
               - np.asarray(dense).reshape(b, c, -1)).max(-1)
    assert d[rows].max() < tol, d[rows].max()


# ---------------------------------------------------------------------------
# one packed mixed step against the full forward pass
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", [40, 80])     # tight, and the lanes' grid
def test_packed_mixed_step_matches_the_full_forward(cfg_params, kv_bits, t):
    """Two prompts at different chunk offsets (one a partial chunk), two
    decode lanes and an idle lane in one mixed step on a row axis of `t`
    rows: each working lane's logits are the full forward pass's at its
    last token (as closely as bf16 pages hold the keys, within a page
    scale's rounding for int8 and int4), and no page outside the working
    lanes' write windows (and the scratch page the idle lane writes)
    changed."""
    cfg, params = cfg_params
    got = packed_mixed_step(cfg, params, kv_bits, t)
    tol = {16: 0.03, 8: 0.06, 4: 0.35}[kv_bits]
    for lane, (_, n) in enumerate(MIXED_LANES):
        if n:
            want = got["want"][lane]
            np.testing.assert_allclose(
                got["logits"][lane, :cfg.vocab], want[:cfg.vocab],
                atol=tol * np.abs(want).max(), err_msg=f"lane {lane}")
    written = set(got["windows"].reshape(-1).tolist()) | {
        kv_pool.SCRATCH_PAGE}
    assert pages_changed(got["before"], got["after"]) <= written
    assert got["counters"] == {}


# ---------------------------------------------------------------------------
# the chunked engine against the dense forward
# ---------------------------------------------------------------------------

def _run(engine, prompts, max_new=6):
    return engine.run(prompts, mode="slow_think", max_new=max_new)


def test_chunked_engine_matches_legacy_fp16(cfg_params):
    """fp16 pools: the chunked mixed-step engine reproduces the plain dense
    greedy loop token-for-token, in exactly two steady-state compilations
    (mixed + decode)."""
    cfg, params = cfg_params
    prompts = [[5, 6, 7], list(range(1, 20)), [9] * 11, [3, 1, 4, 1, 5]]
    ch = make_engine(params, cfg, kv_bits=16, max_batch=4)
    got = _run(ch, prompts)
    assert got.tokens == dense_greedy(params, cfg, prompts, max_new=6)
    assert got.prefill_tokens == sum(got.prompt_lens)
    assert got.mixed_steps > 0
    assert ch.compile_counts() == {"mixed": 1, "decode": 1, "verify": 0}


def test_chunked_engine_first_token_int8(cfg_params):
    """int8 pools: chunked prefill quantizes each chunk once into its pages
    and reads them back, so its first sampled tokens agree with the dense
    bf16 forward's."""
    cfg, params = cfg_params
    prompts = [list(range(1, 20)), [9] * 11, [3, 1, 4, 1, 5]]
    got = _run(make_engine(params, cfg, kv_bits=8), prompts)
    first_dense = [t[0] for t in dense_greedy(params, cfg, prompts,
                                              max_new=6)]
    first_ch = [t[0] for t in got.tokens]
    # the dense forward attends over bf16 K/V; chunked reads the
    # (re-rounded) int8 pages — allow one flip across requests
    agree = sum(a == b for a, b in zip(first_dense, first_ch))
    assert agree >= len(prompts) - 1, (first_dense, first_ch)


def test_chunked_pools_match_oneshot_pools(cfg_params, kv_bits):
    """After chunked prefill, every block's pages *and scales* (bf16 bytes,
    int8 codes, or packed int4 nibbles) equal the one-shot write_prefill of
    the dense forward's K/V for the same prompt."""
    cfg, params = cfg_params
    page, n = 8, 19
    ch = make_engine(params, cfg, kv_bits=kv_bits, max_batch=1,
                     max_seq_len=32)
    # run exactly the prefill portion: submit + step until the first token
    # (the request stays active, so its page-table row still maps it)
    rid = ch.submit(list(range(1, n + 1)), mode="slow_think", max_new=4)
    while not ch.request(rid).out:
        ch.step()
    prompt = ch.request(rid).prompt
    need = -(-len(prompt) // page)
    used = ch.sched.page_table[0][:need]
    assert (used != kv_pool.SCRATCH_PAGE).all()
    toks = np.zeros((1, need * page), np.int32)
    toks[0, :len(prompt)] = prompt
    lens = jnp.asarray([len(prompt)], jnp.int32)
    rows = jnp.asarray(used[None])

    # the dense forward and its one-shot write in one program, as the
    # engine's mixed step is one: XLA then keeps K/V at the same precision
    # up to the quantizer on both sides
    def oneshot(params, toks, pools):
        _, caches = transformer.prefill(
            params, {"tokens": toks, "lengths": lens}, cfg,
            max_len=need * page)
        return {blk: jax.vmap(kv_pool.write_prefill,
                              in_axes=(0, 0, 0, None, None))(
                    pools[blk], c["k"], c["v"], rows, lens)
                for blk, c in caches.items()}

    want = jax.jit(oneshot)(params, jnp.asarray(toks),
                            transformer.init_paged_pools(
                                cfg, ch.sched.alloc.n_pages, page, kv_bits))
    for blk in want:
        for name in pool_leaves(kv_bits):
            np.testing.assert_array_equal(
                np.asarray(ch.pools[blk][name][:, used]),
                np.asarray(want[blk][name][:, used]),
                err_msg=f"block {blk} {name}")


def test_preemption_mid_prefill_preserves_outputs(cfg_params, kv_bits):
    """A pool too small to hold every prompt: requests get evicted while
    *partially prefilled* (pages freed, progress reset), recomputed, and
    still finish with the roomy engine's tokens — the deterministic
    requantization on recompute makes this hold for every pool dtype."""
    cfg, params = cfg_params
    prompts = [list(range(1, 20)), [9] * 17, [3, 1, 4, 1, 5, 9, 2, 6]]
    roomy = make_engine(params, cfg, kv_bits=kv_bits)
    want = _run(roomy, prompts, max_new=8)
    tight = make_engine(params, cfg, kv_bits=kv_bits, n_pages=7)
    got = _run(tight, prompts, max_new=8)
    assert got.evictions > 0
    assert got.tokens == want.tokens
