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
