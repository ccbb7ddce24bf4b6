"""Paged decode-attention regression tests: q_len=1 against a long paged
cache (Pallas interpret kernel vs jnp oracle vs the dense SDPA path), page
pool quantization round-trips, paged-vs-dense engine equivalence, and the
engine's tokens under the Pallas kernels against the XLA read path."""
from types import SimpleNamespace

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from conftest import make_engine
from repro.configs import get_arch, reduced
from repro.kernels.paged_attn import (BLOCK_KEYS, block_pages,
                                      paged_decode_attention,
                                      paged_decode_attention_ref,
                                      pages_walked)
from repro.kernels.paged_prefill import (paged_prefill_attention,
                                         paged_prefill_attention_ref)
from repro.models import attention as attn
from repro.models import transformer
from repro.serving import kv_pool


def make_pool_and_dense(b, t, nkv, hd, page, seed=0, kv_bits=8):
    """A paged pool holding the same K/V as a dense (B,T,nkv,hd) cache."""
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(b, t, nkv, hd)).astype(np.float32)
    v = rng.normal(size=(b, t, nkv, hd)).astype(np.float32)
    n_seq_pages = -(-t // page)
    n_pages = 1 + b * n_seq_pages            # page 0 = scratch
    geom = SimpleNamespace(n_kv_heads=nkv, hd=hd)
    pool = kv_pool.init_pool(geom, n_pages, page, kv_bits=kv_bits)
    page_table = np.zeros((b, n_seq_pages), np.int32)
    ids = iter(range(1, n_pages))
    for i in range(b):
        page_table[i] = [next(ids) for _ in range(n_seq_pages)]
    lengths = jnp.full((b,), t, jnp.int32)
    pool = kv_pool.write_prefill(pool, jnp.asarray(k), jnp.asarray(v),
                                 jnp.asarray(page_table), lengths)
    return pool, jnp.asarray(page_table), jnp.asarray(k), jnp.asarray(v)


# Lane lengths across several compute blocks of the kernels' walk (16-token
# pages, 16 pages a block, a 36-column table: blocks of 16, 16 and 4
# pages): one key, one page, a block, one page past a block, a ragged
# length in the second block, the whole table.
BLOCK_EDGES = (1, 16, BLOCK_KEYS, BLOCK_KEYS + 16, 300, 576)


@pytest.mark.parametrize("b,t,nq,nkv,hd,page,lens", [
    pytest.param(2, 128, 4, 4, 64, 16, None, id="2-128-4-4-64-16"),   # MHA
    pytest.param(3, 96, 8, 2, 32, 16, None, id="3-96-8-2-32-16"),  # GQA 4x
    pytest.param(1, 256, 4, 1, 64, 32, None, id="1-256-4-1-64-32"),  # MQA
    pytest.param(6, 576, 4, 2, 64, 16, BLOCK_EDGES, id="blocks"),
])
@pytest.mark.parametrize("kv_bits", [16, 8, 4])
def test_paged_decode_matches_dense(b, t, nq, nkv, hd, page, lens, kv_bits):
    """q_len=1 against a long paged cache == dense masked SDPA; lengths
    ragged below the table width (default: t, 3t/4, ...)."""
    pool, pt, k, v = make_pool_and_dense(b, t, nkv, hd, page, kv_bits=kv_bits)
    q = jax.random.normal(jax.random.PRNGKey(1), (b, nq, hd), jnp.float32)
    lens = jnp.asarray(lens or [t - i * (t // 4) for i in range(b)],
                       jnp.int32)

    ks, vs = pool.get("k_s"), pool.get("v_s")
    ref = paged_decode_attention_ref(q, pool["k"], pool["v"], ks, vs, pt,
                                     lens)
    got = paged_decode_attention(q, pool["k"], pool["v"], ks, vs, pt, lens,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # dense oracle over the *original* (unquantized) K/V
    mask = (jnp.arange(t)[None, :] < lens[:, None])[:, None, None, :]
    dense = attn._sdpa(q[:, None], k, v, mask, None)[:, 0]   # (B, nq*hd)
    # quant noise grows with narrower codes: bf16 pool / int8 / packed int4
    tol = {16: 0.03, 8: 0.12, 4: 0.5}[kv_bits]
    np.testing.assert_allclose(np.asarray(got).reshape(b, -1),
                               np.asarray(dense), rtol=tol, atol=tol)


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
@pytest.mark.parametrize("kv_bits", [16, 8, 4])
def test_dead_columns_never_read(kernel, kv_bits):
    """Every page-table column at or past ceil(kv_len / page) points at a
    poison page: NaN K/V (bf16 pool) or NaN scales over finite codes
    (int8, packed int4). The kernel's output is finite and equals the
    oracle's over a table whose dead columns hold real pages, so the walk
    never read a dead column (a NaN value row read into a block would
    reach the output through p @ v)."""
    b, t, nq, nkv, hd, page, c = 6, 576, 4, 2, 64, 16, 32
    pool, pt, _, _ = make_pool_and_dense(b, t, nkv, hd, page, seed=2,
                                         kv_bits=kv_bits)
    poison = pool["k"].shape[0]
    grow = lambda x, fill: jnp.concatenate(                 # noqa: E731
        [x, jnp.full((1,) + x.shape[1:], fill, x.dtype)])
    if kv_bits == 16:
        bad = {n: grow(pool[n], jnp.nan) for n in ("k", "v")}
    else:
        bad = {n: grow(pool[n], 17) for n in ("k", "v")}
        bad.update({n: grow(pool[n], jnp.nan) for n in ("k_s", "v_s")})
    clean = {n: grow(x, 0) for n, x in pool.items()}
    kv_len = jnp.asarray(BLOCK_EDGES, jnp.int32)
    live = jnp.arange(pt.shape[1])[None, :] < pages_walked(
        kv_len, page)[:, None]
    pt_bad = jnp.where(live, pt, poison)
    assert block_pages(page, pt.shape[1]) * page == BLOCK_KEYS

    def run(fn, pools, table):
        return fn(pools["k"], pools["v"], pools.get("k_s"),
                  pools.get("v_s"), table)

    if kernel == "decode":
        q = jax.random.normal(jax.random.PRNGKey(4), (b, nq, hd))
        got = run(lambda *a: paged_decode_attention(
            q, *a, kv_len, interpret=True), bad, pt_bad)
        want = run(lambda *a: paged_decode_attention_ref(q, *a, kv_len),
                   clean, pt)
    else:
        n_new = jnp.minimum(kv_len, jnp.asarray([1, c] * (b // 2)))
        q_start = kv_len - n_new
        q = jax.random.normal(jax.random.PRNGKey(4), (b, c, nq, hd))
        got = run(lambda *a: paged_prefill_attention(
            q, *a, q_start, kv_len, interpret=True), bad, pt_bad)
        want = run(lambda *a: paged_prefill_attention_ref(
            q, *a, q_start, kv_len), clean, pt)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_write_token_roundtrip():
    """Decode writes across page boundaries: pool contents must match the
    tokens written, per-page scales tracking the running absmax."""
    page, nkv, hd, b = 8, 2, 16, 2
    geom = SimpleNamespace(n_kv_heads=nkv, hd=hd)
    pool = kv_pool.init_pool(geom, 6, page, kv_bits=8)
    pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    toks = []
    for pos in range(2 * page):
        k = jax.random.normal(jax.random.PRNGKey(pos), (b, nkv, hd)) * (
            1.0 + pos)                        # growing absmax -> requant
        toks.append(k)
        pool = kv_pool.write_token(pool, pt, jnp.full((b,), pos, jnp.int32),
                                   k, k)
    kc, _ = kv_pool.gather_kv(pool, pt)
    want = jnp.stack(toks, 1)                 # (B, T, nkv, hd)
    err = float(jnp.max(jnp.abs(kc.astype(jnp.float32) -
                                want.astype(jnp.float32))))
    # re-rounding drift across successive requants is bounded by a few
    # final-scale quantization steps (scale grows monotonically here)
    step = 2.0 * float(jnp.max(jnp.abs(want))) / 255.0
    assert err < 4 * step, (err, step)


def test_paged_engine_matches_dense_decode():
    """Full-model paged decode (fp16 pool) == the dense decode_step path."""
    cfg = reduced(get_arch("pangu_1b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    b, s, page = 2, 12, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)

    # dense path
    pre = {"tokens": toks[:, :s - 1]}
    l16, caches = transformer.prefill(params, pre, cfg, max_len=s + 4)
    pos = jnp.full((b,), s - 1, jnp.int32)
    d_dense, _ = transformer.decode_step(params, caches, toks[:, s - 1],
                                         pos, cfg)

    # paged path: prefill into pages, then one paged decode step
    n_seq_pages = 4
    pools = transformer.init_paged_pools(cfg, 1 + b * n_seq_pages, page,
                                         kv_bits=16)
    pt = np.zeros((b, n_seq_pages), np.int32)
    ids = iter(range(1, 1 + b * n_seq_pages))
    for i in range(b):
        pt[i] = [next(ids) for _ in range(n_seq_pages)]
    bucket = page * (-(-(s - 1) // page))
    ptoks = np.zeros((b, bucket), np.int32)
    ptoks[:, :s - 1] = np.asarray(toks[:, :s - 1])
    lens = jnp.full((b,), s - 1, jnp.int32)
    _, dense_caches = transformer.prefill(
        params, {"tokens": jnp.asarray(ptoks), "lengths": lens}, cfg,
        max_len=bucket)
    rows = jnp.asarray(pt[:, :bucket // page])
    for i in pools:
        pools[i] = jax.vmap(kv_pool.write_prefill,
                            in_axes=(0, 0, 0, None, None))(
            pools[i], dense_caches[i]["k"], dense_caches[i]["v"], rows, lens)
    d_paged, _ = transformer.decode_step_paged(
        params, pools, jnp.asarray(pt), toks[:, s - 1], pos, cfg)
    np.testing.assert_allclose(np.asarray(d_paged), np.asarray(d_dense),
                               rtol=2e-2, atol=2e-2)


def test_pallas_kernels_serve_the_xla_tokens(cfg_params, kv_bits):
    """The engine with the paged Pallas kernels (interpret mode off the
    chip) emits the same tokens as with the XLA read path, through mixed
    and decode steps."""
    cfg, params = cfg_params
    prompts = [list(range(1, 20)), [9] * 11]
    got = {impl: make_engine(params, cfg, kv_bits=kv_bits, max_batch=2,
                             paged_impl=impl).run(prompts, max_new=8).tokens
           for impl in ("xla", "pallas_interpret")}
    assert got["pallas_interpret"] == got["xla"]
