"""Shared fixtures for the serving/engine test suite.

One tiny reduced model is initialized per session (`cfg_params`) instead of
per test file, `make_engine` is the single engine factory the engine tests
build on, and `dense_greedy` is the plain dense-cache loop they are
checked against. The `kv_bits` fixture parameterizes over every pool dtype —
bf16, int8, and packed int4 — so engine-level guarantees (chunked prefill,
prefix caching, preemption, speculative decode) are exercised under all
three without per-file copy-paste.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import transformer
from repro.serving import ContinuousBatchingEngine, cot

ALL_KV_BITS = (16, 8, 4)       # bf16 / int8 / packed-int4 pool dtypes
QUANT_KV_BITS = (8, 4)         # the quantized pools (k_s/v_s scale leaves)


@pytest.fixture(scope="session")
def cfg_params():
    """Reduced pangu_1b config + params, shared across the whole session
    (read-only — tests must not mutate either)."""
    cfg = reduced(get_arch("pangu_1b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(params=ALL_KV_BITS)
def kv_bits(request):
    """Every pool dtype: 16 (bf16), 8 (int8), 4 (packed int4)."""
    return request.param


def make_engine(params, cfg, *, page_size=8, max_batch=3, max_seq_len=64,
                **kw):
    """The continuous-batching engine with the tiny-test geometry defaults
    the engine tests share; any engine kwarg (kv_bits, n_pages,
    prefix_cache, spec_decode, ...) can be overridden."""
    return ContinuousBatchingEngine(params, cfg, page_size=page_size,
                                    max_batch=max_batch,
                                    max_seq_len=max_seq_len, **kw)


def pool_leaves(kv_bits):
    """The pool leaf names a dtype carries (quantized pools add scales)."""
    return ("k", "v", "k_s", "v_s") if kv_bits != 16 else ("k", "v")


def dense_greedy(params, cfg, prompts, *, mode="slow_think", max_new=32,
                 **model_kw):
    """The engines' oracle: greedy generations from a plain loop over dense
    caches, independent of the paged pool, the scheduler and the chunked
    steps. Each prompt gets the mode's directive (`cot.apply_mode`); the
    prompts prefill together, left-aligned with their lengths
    (`transformer.prefill`), then `decode_step` and argmax run until each
    request has the budget `cot.budget_for` gives it. model_kw (qcfg,
    impl) go to both model calls."""
    full = [cot.apply_mode(p, mode, cfg.vocab) for p in prompts]
    budgets = [cot.budget_for(mode, len(p), max_new) for p in full]
    lens = np.array([len(p) for p in full], np.int32)
    toks = np.zeros((len(full), lens.max()), np.int32)
    for i, p in enumerate(full):
        toks[i, :len(p)] = p
    n = max(budgets)
    prefill = jax.jit(partial(transformer.prefill, cfg=cfg,
                              max_len=int(lens.max()) + n, **model_kw))
    decode = jax.jit(partial(transformer.decode_step, cfg=cfg, **model_kw))
    logits, caches = prefill(params, {"tokens": jnp.asarray(toks),
                                      "lengths": jnp.asarray(lens)})
    out = []
    for i in range(n):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(np.asarray(tok))
        if i + 1 < n:
            logits, caches = decode(params, caches, tok,
                                    jnp.asarray(lens + i))
    gen = np.stack(out, 1)
    return [gen[i, :b].tolist() for i, b in enumerate(budgets)]


# One packed mixed step against the one-shot reference (the equivalence
# tests of tests/test_chunked_prefill.py and tests/test_mla_moe.py): lanes
# as (tokens in the pool before the step, real tokens of the step) — a
# prompt's second full chunk, another prompt's partial first chunk, a
# decode lane mid-page, a decode lane at a page's first slot, an idle lane.
MIXED_PAGE, MIXED_CHUNK = 8, 16
MIXED_LANES = ((16, 16), (0, 9), (13, 1), (8, 1), (0, 0))


def packed_mixed_step(cfg, params, kv_bits, t):
    """Write the lanes' earlier tokens with one mixed step, then run the
    mixed step of MIXED_LANES on a row axis of `t` rows, both through
    `transformer.prefill_chunk_paged` from pools of noise (so that a stray
    write into any page shows). Returns a dict: "logits" (B, V) of the step
    under test, "want" (B, V) the full forward pass's logits at each
    working lane's last token (zeros for the idle lane), "counters" the
    expert layers' counters, "tokens" the step's real tokens, "before" and
    "after" its pools, "windows" the pages its working lanes write."""
    from repro.serving import kv_pool
    page, c = MIXED_PAGE, MIXED_CHUNK
    b, cols = len(MIXED_LANES), 5
    wc = kv_pool.chunk_window_pages(c, page)
    table = np.arange(1, 1 + b * cols, dtype=np.int32).reshape(b, cols)
    table[-1] = kv_pool.SCRATCH_PAGE                    # the idle lane
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(11), (b, 2 * c), 1, cfg.vocab - 8), np.int32)
    pools = transformer.init_paged_pools(cfg, 1 + b * cols, page, kv_bits)
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 64))

    def noise(a):
        k = next(keys)
        if a.dtype in (jnp.int8, jnp.uint8):
            return jax.random.randint(k, a.shape, 0, 255).astype(a.dtype)
        if a.dtype == jnp.float32:                      # scales
            return jax.random.uniform(k, a.shape, minval=0.01, maxval=0.1)
        return jax.random.normal(k, a.shape).astype(a.dtype)

    step = jax.jit(partial(transformer.prefill_chunk_paged, cfg=cfg,
                           with_stats=True))

    def run(pools, lanes):
        q_start = np.asarray([q for q, _ in lanes], np.int32)
        n_new = np.asarray([n for _, n in lanes], np.int32)
        chunk = np.zeros((b, c), np.int32)
        windows = np.full((b, wc), kv_pool.SCRATCH_PAGE, np.int32)
        for i, (q, n) in enumerate(lanes):
            chunk[i, :n] = toks[i, q:q + n]
            if n:
                windows[i, :min(wc, cols - q // page)] = \
                    table[i, q // page:q // page + wc]
        rows = transformer.pack_rows(chunk, q_start, n_new, t)
        out = step(params, pools, jnp.asarray(table), jnp.asarray(windows),
                   *map(jnp.asarray, rows), jnp.asarray(q_start),
                   jnp.asarray(n_new))
        return out, windows[n_new > 0]

    (_, before, _), _ = run(jax.tree.map(noise, pools),
                            [(0, q) for q, _ in MIXED_LANES])
    (logits, after, counters), windows = run(jax.tree.map(jnp.copy, before),
                                             MIXED_LANES)
    want = np.zeros(logits.shape, np.float32)
    for i, (q, n) in enumerate(MIXED_LANES):
        if n:
            full, _ = transformer.forward_train(
                params, {"tokens": jnp.asarray(toks[i:i + 1, :q + n])}, cfg,
                remat=False)
            want[i] = np.asarray(full[0, q + n - 1])
    return {"logits": np.asarray(logits), "want": want, "counters": counters,
            "tokens": sum(n for _, n in MIXED_LANES), "before": before,
            "after": after, "windows": windows}


def pages_changed(before, after):
    """The pool pages (flat ids, any layer) that any leaf changed at."""
    changed = set()
    for name in before:
        for leaf in before[name]:
            a = np.asarray(before[name][leaf]).astype(np.float32)
            z = np.asarray(after[name][leaf]).astype(np.float32)
            d = (a != z).reshape(a.shape[:2] + (-1,)).any(-1)    # (L, N)
            changed |= set(np.flatnonzero(d.any(0)).tolist())
    return changed
