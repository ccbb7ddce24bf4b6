"""Pure-jnp oracles for every Pallas kernel in this package.

Each `*_ref` function defines the exact numerical contract its kernel must
match (tests assert allclose between `interpret=True` kernel execution and
these references across shape/dtype sweeps).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant import qtypes
from repro.core.quant.hadamard import block_hadamard_matmul


# ---------------------------------------------------------------------------
# INT8 (W8A8) GEMM with fused dequant epilogue
# ---------------------------------------------------------------------------

def int8_matmul_ref(x_q: jax.Array, w_q: jax.Array,
                    x_scale: jax.Array, w_scale: jax.Array,
                    out_dtype=jnp.float32) -> jax.Array:
    """(M,K) int8 @ (K,N) int8 -> int32 accum -> * x_scale (M,1) * w_scale (1,N)."""
    acc = jax.lax.dot_general(
        x_q, w_q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * x_scale * w_scale).astype(out_dtype)


# ---------------------------------------------------------------------------
# W4A8 GEMM: packed int4 weights, per-group scales, int8 activations
# ---------------------------------------------------------------------------

def w4a8_matmul_ref(x_q: jax.Array, w_packed: jax.Array,
                    x_scale: jax.Array, w_group_scale: jax.Array,
                    group_size: int, out_dtype=jnp.float32) -> jax.Array:
    """x_q: (M,K) int8; w_packed: (K//2,N) int8 in 'halves' layout;
    w_group_scale: (K//G, N) f32; x_scale: (M,1) f32.

    Contract: int32 accumulation within each K-group, float32 across groups
    (matches the kernel's per-group dequant epilogue).
    """
    k = x_q.shape[1]
    n = w_packed.shape[1]
    g = group_size
    w_q = qtypes.unpack_int4_halves(w_packed, g)          # (K, N) int4-valued
    xg = x_q.reshape(x_q.shape[0], k // g, g)
    wg = w_q.reshape(k // g, g, n)
    # int32 accumulate per group
    acc_g = jnp.einsum("mgk,gkn->mgn", xg.astype(jnp.int32), wg.astype(jnp.int32))
    out = jnp.einsum("mgn,gn->mn", acc_g.astype(jnp.float32),
                     w_group_scale.astype(jnp.float32))
    return (out * x_scale).astype(out_dtype)


# ---------------------------------------------------------------------------
# Dynamic per-token activation quantization (optionally fused smooth / FWHT)
# ---------------------------------------------------------------------------

def quantize_act_ref(x: jax.Array,
                     smooth: Optional[jax.Array] = None,
                     hadamard_block: int = 0):
    """x: (M, K) float -> (q int8 (M,K), scale f32 (M,1)).

    Pipeline (paper §3.2): X <- X / s  (SmoothQuant), X <- X H (rotation),
    then symmetric per-token quantization (Eq. 2).
    """
    t = x.astype(jnp.float32)
    if smooth is not None:
        t = t / smooth.astype(jnp.float32)
    if hadamard_block:
        t = block_hadamard_matmul(t, hadamard_block)
    q, scale = qtypes.quantize_act(t, bits=8, granularity="per_token")
    return q, scale


def fused_rmsnorm_quant_ref(x: jax.Array, gamma: jax.Array, eps: float = 1e-6,
                            smooth: Optional[jax.Array] = None):
    """Beyond-paper fused epilogue: RMSNorm -> (smooth) -> per-token quant."""
    xf = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    t = xf / rms * gamma.astype(jnp.float32)
    if smooth is not None:
        t = t / smooth.astype(jnp.float32)
    q, scale = qtypes.quantize_act(t, bits=8, granularity="per_token")
    return q, scale


# ---------------------------------------------------------------------------
# Block Walsh-Hadamard transform
# ---------------------------------------------------------------------------

def hadamard_ref(x: jax.Array, block: int = 128) -> jax.Array:
    return block_hadamard_matmul(x, block)


# ---------------------------------------------------------------------------
# INT8 KV-cache attention helpers (beyond-paper: quantized KV)
# ---------------------------------------------------------------------------

def kv_dequant_ref(k_q: jax.Array, k_scale: jax.Array) -> jax.Array:
    """Per (token, head) scales: k_q (..., S, H, D) int8, k_scale (..., S, H, 1)."""
    return k_q.astype(jnp.float32) * k_scale


# ---------------------------------------------------------------------------
# Chunked-prefill attention over paged KV (beyond-paper: batched prefill)
# ---------------------------------------------------------------------------

PAGED_NEG_INF = -1e30


def paged_prefill_attention_ref(q, k_pages, v_pages, k_scale, v_scale,
                                page_table, q_start, kv_lengths):
    """Chunk-query causal attention against the paged (optionally int8) KV
    pool — the XLA serving path and the contract the Pallas kernel in
    `paged_prefill.py` is pinned to.

    q: (B, C, nq, hd) chunk queries, query i at absolute position
    q_start[b] + i; k_pages/v_pages: (P, nkv, page, hd) int8 or float, or
    (P, nkv, page, hd // 2) uint8 packed int4;
    k_scale/v_scale: (P, nkv) f32 per-(page, head) scales (int8 pools) or
    None; page_table: (B, W) physical page ids; q_start: (B,);
    kv_lengths: (B,) valid keys including the in-flight chunk (>= 1).
    Query i sees keys at kpos <= q_start[b] + i with kpos < kv_lengths[b].
    Returns (B, C, nq, hd) in q.dtype.
    """
    return _chunk_attend(q, _read_pages(k_pages, k_scale, page_table),
                         _read_pages(v_pages, v_scale, page_table),
                         q_start, kv_lengths)


def _read_pages(pages, scales, page_table):
    """Gather + dequantize a page table's worth of KV: (B, W*page, nkv, hd)
    f32 (hd is twice the stored last dim for packed int4 pages), through
    the pool's own `kv_pool.dequant_pages`."""
    # Lazy import: repro.serving's package init reaches this module.
    from repro.serving.kv_pool import dequant_pages
    quantized = pages.dtype in (jnp.int8, jnp.uint8)
    return dequant_pages(pages[page_table],
                         scales[page_table] if quantized else None)


def _chunk_attend(q, k, v, q_start, kv_lengths):
    """Causal chunk-query attention over dense per-sequence keys: query i
    (absolute position q_start[b] + i) sees kpos <= q_start[b] + i with
    kpos < kv_lengths[b].

    GQA is expressed with an explicit group axis (einsum broadcasts the
    shared K/V head over its `hper` queries) rather than jnp.repeat —
    materializing the repeated K/V costs ~2x the whole attention on the
    XLA CPU path, and the grouped contraction is bitwise identical (the
    per-(query, key) dot over hd is unchanged)."""
    b, c, nq, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    hper = nq // nkv
    qg = q.reshape(b, c, nkv, hper, hd).astype(jnp.float32) / (hd ** 0.5)
    scores = jnp.einsum("bcgph,btgh->bgpct", qg, k)
    kpos = jnp.arange(t)[None, None, None, None, :]
    qpos = (q_start[:, None] + jnp.arange(c)[None, :])[:, None, None, :, None]
    mask = (kpos <= qpos) & (kpos < kv_lengths[:, None, None, None, None])
    scores = jnp.where(mask, scores, PAGED_NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgpct,btgh->bcgph", probs, v)
    return out.reshape(b, c, nq, hd).astype(q.dtype)


def paged_verify_attention_ref(q, k_pages, v_pages, k_scale, v_scale,
                               page_table, q_start, n_new, k_win, v_win):
    """Multi-query-per-sequence decode (speculative verify), read-only on
    the pool: the C-token draft window's raw K/V projections (k_win/v_win,
    (B, C, nkv, hd)) are spliced over the gathered past keys at positions
    q_start..q_start+C-1 instead of being written into pages first, so a
    rejected draft never touches the pool. The valid-key horizon is the
    window end — kv_lengths = q_start + n_new — and the causal chunk mask
    handles the intra-window triangle; C need not be page-aligned (k+1
    draft tokens); max(.., 1) keeps idle lanes (n_new == 0) finite so
    their garbage rows still softmax over a nonempty prefix.

    For float pools the splice is bit-identical to a write + paged read
    (the page round trip is a no-op cast); for int8 pools the window skips
    one quantize-dequantize round trip, so verify logits can differ from
    the written-then-read chain within quantization noise."""
    c = q.shape[1]
    page = k_pages.shape[2]
    kv_lengths = jnp.maximum(q_start + n_new, 1)
    # extend the *table* (not the gathered data) by enough pages that the
    # per-batch splice never clamps near the end of a full sequence
    # (q_start <= W*page - 1 by the scheduler's capacity invariant): the
    # pad columns only ever hold window rows >= n_new, which kv_lengths
    # masks off, so any valid page id works as filler
    pad = -(-max(c - 1, 1) // page)
    ext = jnp.concatenate([page_table] + [page_table[:, :1]] * pad, axis=1)

    def inject(pages, scales, wnd):
        dense = _read_pages(pages, scales, ext)
        return jax.vmap(
            lambda db, wb, s: jax.lax.dynamic_update_slice(
                db, wb.astype(db.dtype), (s, 0, 0)))(dense, wnd, q_start)

    k = inject(k_pages, k_scale, k_win)
    v = inject(v_pages, v_scale, v_win)
    return _chunk_attend(q, k, v, q_start, kv_lengths)
