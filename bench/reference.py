"""Plain reference of a served decoder, with the quantization the
configuration states, for the check that decides `correct`. This module
holds what every architecture shares: the integer and bfloat16 arithmetic
of a linear, the paged store of keys and values, the blocked causal
attention over it, RMSNorm and RoPE, the embedding, the LM head and the
walk over a sequence. An architecture's layer equations and its loop over
layers are in its package, `bench/arch/<arch>/` (`forward`). Nothing here
imports the program or takes anything the program made: the float32
weights come again from the seed (`bench/weights.py` and the
architecture's own), one layer at a time, and every scale is computed here.

What it computes, for one sequence (prompt as served + served tokens), in
float32 with matmuls at HIGHEST precision:
  * activations rounded to bfloat16 where the configuration keeps them in
    bfloat16 (the residual stream, each norm's and each linear's output);
  * every linear as symmetric integer arithmetic (or, with 16-bit weights
    and activations, a bfloat16 matmul): per-token activation
    codes (act_bits) times per-channel or per-group weight codes
    (weight_bits), scale s = 2 max|x| / (2^bits - 1), codes clipped to
    +-(2^(bits-1) - 1), products summed exactly in int32;
  * keys and values stored in pages of `page` tokens with one scale per
    (page, kv head) (kv_bits 8 or 4; 16 stores them unquantized). Pages
    are written as the serving path writes them: the prompt in chunks
    that begin on page boundaries (each prompt page quantized once), then
    one token at a time, each write dequantizing the page, adding the
    token and quantizing the page again. A query sees the pages before
    its own in their final state and its own page as it was right after
    its own token was written;
  * logits in float32 over the real vocabulary.

`precision` is a dict {weight_bits, weight_group, act_bits, kv_bits}. The
control of the check is this same reference at a lower precision.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256            # query rows per attention block
LOGIT_ROWS = 512         # rows per block of the LM head


def bf(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _scale(absmax, bits):
    return jnp.maximum(2.0 * absmax / (2 ** bits - 1), 1e-8)


def _codes(x, s, bits):
    lim = 2 ** (bits - 1) - 1
    return jnp.clip(jnp.round(x / s), -lim, lim)


def rms_norm(x, g, eps):
    return bf(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g)


def rope(x, pos, theta):
    """x (T, H, hd); rotate-half RoPE at absolute positions pos (T,)."""
    hd = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return bf(jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1))


def linear(x, w, p):
    """x (T, K) bfloat16-valued; w (K, N) float32 -> (T, N) float32. With
    16-bit weights and activations, a bfloat16 matmul summed in float32."""
    k, n = w.shape
    if p["weight_bits"] >= 16 or p["act_bits"] >= 16:
        if p["weight_bits"] < 16 or p["act_bits"] < 16:
            raise ValueError(f"no integer path for {p}")
        return jnp.dot(x, bf(w), precision=HI)
    sa = _scale(jnp.max(jnp.abs(x), -1, keepdims=True), p["act_bits"])
    qa = _codes(x, sa, p["act_bits"]).astype(jnp.int8)
    bits, g = p["weight_bits"], p["weight_group"]
    if not g:
        sw = _scale(jnp.max(jnp.abs(w), 0, keepdims=True), bits)
        qw = _codes(w, sw, bits).astype(jnp.int8)
        acc = jnp.dot(qa, qw, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * sa * sw
    wg = w.reshape(k // g, g, n)
    sw = _scale(jnp.max(jnp.abs(wg), 1), bits)                  # (K/g, N)
    qw = _codes(wg, sw[:, None, :], bits).astype(jnp.int8)
    qa_g = qa.reshape(x.shape[0], k // g, g).swapaxes(0, 1)     # (K/g, T, g)

    def group(acc, i):
        part = jnp.dot(qa_g[i], qw[i], preferred_element_type=jnp.int32)
        return acc + part.astype(jnp.float32) * sw[i], None

    acc, _ = jax.lax.scan(group, jnp.zeros((x.shape[0], n), jnp.float32),
                          jnp.arange(k // g))
    return acc * sa


def _page_quant(x, bits):
    """x (..., page, nkv, hd) -> dequantized pages, one scale per head."""
    s = _scale(jnp.max(jnp.abs(x), axis=(-3, -1), keepdims=True), bits)
    return _codes(x, s, bits) * s


def paged_kv(x, n_real, l0, page, bits):
    """Stored keys (or values) x (T, nkv, hd), T a multiple of `page`, of a
    sequence of n_real tokens whose first l0 were written as prompt.
    Returns (final (T, nkv, hd), own (page, T // page, page, nkv, hd)):
    the pages in their final state, and own[j, p] = page p as it was right
    after the write of its slot j."""
    t, nkv, hd = x.shape
    n_pages = t // page
    pos = jnp.arange(t).reshape(n_pages, page)
    xp = x.reshape(n_pages, page, nkv, hd)
    if bits == 16:
        own = jnp.broadcast_to(xp, (page,) + xp.shape)
        return x, own
    prompt = jnp.where((pos < l0)[..., None, None], xp, 0.0)
    state = _page_quant(prompt, bits)
    slots = jnp.arange(page)

    def write(state, j):
        p = pos[:, j]
        is_write = (p >= l0) & (p < n_real)
        merged = jnp.where((slots < j)[None, :, None, None], state, 0.0)
        merged = merged.at[:, j].set(xp[:, j])
        new = _page_quant(merged, bits)
        state = jnp.where(is_write[:, None, None, None], new, state)
        return state, state

    final, own = jax.lax.scan(write, state, slots)
    return final.reshape(t, nkv, hd), own


def attention(q, k, v, n_real, l0, page, bits):
    """q (T, nq, hd); k, v (T, nkv, hd), causal over the paged store."""
    t, nq, hd = q.shape
    nkv = k.shape[1]
    hper = nq // nkv
    kf, kown = paged_kv(k, n_real, l0, page, bits)
    vf, vown = paged_kv(v, n_real, l0, page, bits)
    qg = q.reshape(t, nkv, hper, hd) / np.sqrt(hd)
    keys = jnp.arange(t)

    def block(start):
        rows = start + jnp.arange(Q_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(qg, start, Q_BLOCK)
        page_of, slot_of = rows // page, rows % page
        ko = kown[slot_of, page_of]                   # (Qb, page, nkv, hd)
        vo = vown[slot_of, page_of]
        s_main = jnp.einsum("tgph,sgh->tgps", qb, kf, precision=HI)
        s_main = jnp.where((keys[None, :] < (page_of * page)[:, None])
                           [:, None, None, :], s_main, -jnp.inf)
        s_own = jnp.einsum("tgph,tjgh->tgpj", qb, ko, precision=HI)
        s_own = jnp.where((jnp.arange(page)[None, :] <= slot_of[:, None])
                          [:, None, None, :], s_own, -jnp.inf)
        m = jnp.maximum(s_main.max(-1, keepdims=True),
                        s_own.max(-1, keepdims=True))
        e_main, e_own = jnp.exp(s_main - m), jnp.exp(s_own - m)
        den = e_main.sum(-1, keepdims=True) + e_own.sum(-1, keepdims=True)
        out = (jnp.einsum("tgps,sgh->tgph", e_main, vf, precision=HI)
               + jnp.einsum("tgpj,tjgh->tgph", e_own, vo, precision=HI))
        return (out / den).reshape(Q_BLOCK, nq * hd)

    out = jax.lax.map(block, jnp.arange(0, t, Q_BLOCK))
    return out.reshape(t, nq * hd)


@functools.lru_cache(maxsize=None)
def _programs(m_items):
    m = dict(m_items)

    @jax.jit
    def embed(key, tokens):
        return bf(weights.embed(key, m))[tokens]

    @jax.jit
    def head(x, key, rows, served):
        """Reference logits at `rows` (R,): (max, logit of `served`,
        argmax), over the real vocabulary."""
        h = rms_norm(x[rows], weights.final_norm(key, m), m["rms_norm_eps"])
        w = (weights.embed(key, m).T if m["tie_word_embeddings"]
             else weights.lm_head(key, m))
        logits = jnp.dot(h, bf(w), precision=HI)
        best = logits.max(-1)
        got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
        return best, got, logits.argmax(-1).astype(jnp.int32)

    return embed, head


def padded_len(max_seq_len: int) -> int:
    """Positions the reference runs a sequence at: the longest sequence a
    lane holds, rounded up to whole query blocks."""
    return -(-max_seq_len // Q_BLOCK) * Q_BLOCK


def logits_summary(arch, seed: int, m: dict, p: dict, page: int,
                   padded: int, prompt: Sequence[int], served: Sequence[int],
                   judge: Sequence[int] = ()) -> Dict[str, np.ndarray]:
    """Run the reference of architecture `arch` (its package, as
    `bench.run.load_arch` loads it) over prompt + served tokens (padded to
    `padded` positions, a multiple of Q_BLOCK). At each position that
    produced a served token returns the reference's best logit, its logit
    of the served token, and its argmax;
    with `judge` tokens given (another precision's choices), also its logit
    of each of those."""
    embed, head = _programs(tuple(sorted(m.items())))
    key = weights.base_key(seed)
    seq = list(prompt) + list(served)
    n_real, l0 = len(seq), len(prompt)
    if n_real > padded:
        raise ValueError(f"sequence of {n_real} tokens > {padded}")
    toks = np.zeros(padded, np.int32)
    toks[:n_real] = seq
    x = embed(key, jnp.asarray(toks))
    x = arch.forward(x, key, m, p, page, n_real, l0)
    out: Dict[str, List[np.ndarray]] = {"best": [], "served": [],
                                        "argmax": [], "judged": []}
    n = len(served)
    for lo in range(0, n, LOGIT_ROWS):
        rows = np.full(LOGIT_ROWS, l0 - 1, np.int32)
        cnt = min(LOGIT_ROWS, n - lo)
        rows[:cnt] = l0 - 1 + np.arange(lo, lo + cnt)
        tok = np.zeros(LOGIT_ROWS, np.int32)
        tok[:cnt] = served[lo:lo + cnt]
        best, got, arg = head(x, key, jnp.asarray(rows), jnp.asarray(tok))
        out["best"].append(np.asarray(best)[:cnt])
        out["served"].append(np.asarray(got)[:cnt])
        out["argmax"].append(np.asarray(arg)[:cnt])
        if len(judge):
            jt = np.zeros(LOGIT_ROWS, np.int32)
            jt[:cnt] = judge[lo:lo + cnt]
            _, jg, _ = head(x, key, jnp.asarray(rows), jnp.asarray(jt))
            out["judged"].append(np.asarray(jg)[:cnt])
    return {k: np.concatenate(v) if v else np.zeros(0) for k, v in
            out.items()}
