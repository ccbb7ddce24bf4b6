"""Serving-path quantization tests: int8 KV cache fidelity, quantized
prefill/decode equivalence, engine with variable-length batches, and the
speculative-verify path against a dense fp32 oracle for every paged pool
dtype (bf16 / int8 / packed int4)."""
from types import SimpleNamespace

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_arch, reduced
from repro.core.quant import INT8, calibrate, ptq
from repro.kernels.ref import paged_verify_attention_ref
from repro.models import attention as attn
from repro.models import transformer
from repro.serving import ContinuousBatchingEngine, kv_pool


def setup(arch="qwen3_0_6b", s=16, b=2):
    cfg = reduced(get_arch(arch))
    key = jax.random.PRNGKey(0)
    params = transformer.init_params(key, cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    return cfg, params, toks


@pytest.mark.parametrize("arch", ["qwen3_0_6b", "mixtral_8x7b"])
def test_int8_kv_cache_close_to_fp(arch):
    """decode with the int8-quantized KV cache stays close to the bf16
    cache (beyond-paper W8A8KV8 path used by the 90B decode cells)."""
    cfg, params, toks = setup(arch)
    b, s = toks.shape
    pre = {"tokens": toks[:, :s - 1]}
    last = toks[:, s - 1]
    pos = jnp.full((b,), s - 1, jnp.int32)

    l16, c16 = transformer.prefill(params, pre, cfg, max_len=s + 2,
                                   kv_bits=16)
    d16, _ = transformer.decode_step(params, c16, last, pos, cfg)
    l8, c8 = transformer.prefill(params, pre, cfg, max_len=s + 2, kv_bits=8)
    d8, _ = transformer.decode_step(params, c8, last, pos, cfg)
    # logits close; top-1 identical for a random-init model's margins
    np.testing.assert_allclose(np.asarray(d8), np.asarray(d16), atol=0.15,
                               rtol=0.1)
    agree = float(jnp.mean(jnp.argmax(d8, -1) == jnp.argmax(d16, -1)))
    assert agree >= 0.5, agree


def test_rolling_window_cache_decode_matches_forward():
    """SWA rolling cache beyond the window: decode at pos > window must
    equal the full forward with window masking (mixtral long-context)."""
    cfg = reduced(get_arch("mixtral_8x7b"))
    assert cfg.sliding_window and cfg.sliding_window < 64
    w = cfg.sliding_window
    s = w * 2 + 5              # sequence well past the window
    params = transformer.init_params(jax.random.PRNGKey(3), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, s), 0, cfg.vocab)

    logits_full, _ = transformer.forward_train(params, {"tokens": toks},
                                               cfg, remat=False)
    pre = {"tokens": toks[:, :s - 1]}
    _, caches = transformer.prefill(params, pre, cfg, max_len=s + 2)
    pos = jnp.full((1,), s - 1, jnp.int32)
    dec, _ = transformer.decode_step(params, caches, toks[:, s - 1], pos, cfg)
    np.testing.assert_allclose(np.asarray(dec),
                               np.asarray(logits_full[:, s - 1]),
                               rtol=3e-2, atol=3e-2)


def test_engine_variable_length_prompts_quantized():
    cfg, params, _ = setup("qwen3_0_6b")
    batches = [{"tokens": jax.random.randint(jax.random.PRNGKey(9), (2, 24),
                                             0, cfg.vocab)}]
    stats = calibrate.collect_stats(params, batches, cfg)
    pq = ptq.quantize_model(params, cfg, INT8, stats)
    eng = ContinuousBatchingEngine(pq, cfg, qcfg=INT8, impl="xla")
    prompts = [[5, 6, 7], list(range(1, 20)), [9] * 11]
    res = eng.run(prompts, mode="slow_think", max_new=6)
    assert len(res.tokens) == 3
    assert all(len(t) == 6 for t in res.tokens)
    assert all(0 <= tok < cfg.vocab for t in res.tokens for tok in t)


# ---------------------------------------------------------------------------
# speculative verify vs dense fp32, all paged pool dtypes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_bits", [16, 8, 4])
def test_paged_verify_matches_dense_fp32(kv_bits):
    """The verify path (raw draft window spliced over a quantized paged
    history, ragged n_new) reproduces dense fp32 causal attention within
    the pool dtype's quantization noise — only the history round-trips
    through pages, so the bound is the same family as the decode kernel's."""
    b, c, nq, nkv, hd, page = 2, 4, 4, 2, 16, 8
    q_start = np.asarray([13, 7], np.int32)       # unaligned page boundaries
    n_new = np.asarray([4, 3], np.int32)          # one lane partially idle
    t = int(q_start.max()) + c
    w = -(-t // page)
    bucket = w * page                             # write_prefill page bucket
    rng = np.random.default_rng(5)

    # history raw K/V, zeroed past each row's q_start (write_prefill masks
    # by lengths too; the oracle below needs the same zeros)
    hist_k = rng.normal(size=(b, bucket, nkv, hd)).astype(np.float32)
    hist_v = rng.normal(size=(b, bucket, nkv, hd)).astype(np.float32)
    live = (np.arange(bucket)[None, :, None, None]
            < q_start[:, None, None, None])
    hist_k, hist_v = hist_k * live, hist_v * live
    k_win = rng.normal(size=(b, c, nkv, hd)).astype(np.float32)
    v_win = rng.normal(size=(b, c, nkv, hd)).astype(np.float32)
    q = rng.normal(size=(b, c, nq, hd)).astype(np.float32)

    geom = SimpleNamespace(n_kv_heads=nkv, hd=hd)
    pool = kv_pool.init_pool(geom, 1 + b * w, page, kv_bits=kv_bits)
    pt = np.arange(1, 1 + b * w, dtype=np.int32).reshape(b, w)
    pool = kv_pool.write_prefill(pool, jnp.asarray(hist_k),
                                 jnp.asarray(hist_v), jnp.asarray(pt),
                                 jnp.asarray(q_start))

    got = np.asarray(paged_verify_attention_ref(
        jnp.asarray(q), pool["k"], pool["v"], pool.get("k_s"),
        pool.get("v_s"), jnp.asarray(pt), jnp.asarray(q_start),
        jnp.asarray(n_new), jnp.asarray(k_win), jnp.asarray(v_win)))

    # dense fp32 oracle: splice the raw window over raw history
    hper = nq // nkv
    tol = {16: 0.03, 8: 0.12, 4: 0.5}[kv_bits]
    for i in range(b):
        keys, vals = hist_k[i].copy(), hist_v[i].copy()
        keys[q_start[i]:q_start[i] + c] = k_win[i]
        vals[q_start[i]:q_start[i] + c] = v_win[i]
        kr = np.repeat(keys, hper, axis=1)        # (bucket, nq, hd)
        vr = np.repeat(vals, hper, axis=1)
        scores = np.einsum("cqh,tqh->qct", q[i] / hd ** 0.5, kr)
        kpos = np.arange(bucket)[None, None, :]
        qpos = (q_start[i] + np.arange(c))[None, :, None]
        mask = (kpos <= qpos) & (kpos < q_start[i] + n_new[i])
        scores = np.where(mask, scores, -1e30)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        want = np.einsum("qct,tqh->cqh", probs, vr)
        # rows past n_new are masked lanes with garbage-by-contract outputs
        np.testing.assert_allclose(got[i, :n_new[i]], want[:n_new[i]],
                                   rtol=tol, atol=tol)


def test_decode_mask_rolling_positions():
    """Rolling-slot position recovery: slots hold the right absolute keys."""
    c = {"k": jnp.zeros((2, 8, 1, 4)), "v": jnp.zeros((2, 8, 1, 4))}
    m = attn.decode_mask(c, jnp.array([10, 3]), window=8)[:, 0, 0]
    # request 0 at pos 10, window 8: valid keys are pos 3..10 -> all slots
    assert bool(m[0].all())
    # request 1 at pos 3: only slots 0..3 valid (pos 0..3)
    np.testing.assert_array_equal(
        np.asarray(m[1]), [True, True, True, True, False, False, False,
                           False])
