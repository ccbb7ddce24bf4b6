"""Pallas TPU kernel: chunked-prefill attention over paged KV with inline
int8 / packed-int4 dequant.

Chunk-shaped attention (q_len = C tokens per sequence) against the paged KV
pool of serving/kv_pool.py — the read half of the fused quantize-on-write
prefill path: each chunk's K/V has already been quantized into its pages by
`kv_pool.write_chunk`, and this kernel attends causally over everything
written so far (earlier chunks + the in-flight chunk) without ever
materializing a dense cache.

It is the page walk of `paged_attn.paged_attention_call` (one grid step per
lane, a loop over the lane's live blocks of pages, double-buffered manual
DMAs of whole pages), with two differences from the decode kernel:

  * the query block is the whole chunk — GQA query heads fold into rows as
    (C * hper, hd), row r belonging to chunk token r // hper;
  * the mask is causal *within* the chunk: row r at absolute position
    q_start[b] + r // hper sees keys kpos <= that position (and
    kpos < kv_lengths[b], which covers slots riding the mixed step with
    fewer than C valid tokens — their extra rows attend a nonempty prefix
    and are discarded by the caller).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attn import paged_attention_call
from repro.kernels.ref import paged_prefill_attention_ref  # noqa: F401 oracle


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention(q, k_pages, v_pages, k_scale, v_scale,
                            page_table, q_start, kv_lengths, *,
                            interpret: bool = False):
    """q: (B, C, nq, hd) chunk queries at positions q_start[b] + i;
    k_pages/v_pages: (P, nkv, page, hd) int8/float or (P, nkv, page, hd//2)
    uint8 packed int4; k_scale/v_scale: (P, nkv) f32 (quantized pools) or
    None; page_table: (B, W) physical ids; q_start: (B,); kv_lengths: (B,)
    valid keys (>= 1, at most W * page). Returns (B, C, nq, hd) in q.dtype.
    Same contract as `ref.paged_prefill_attention_ref`."""
    b, c, nq, hd = q.shape
    nkv = k_pages.shape[1]
    hper = nq // nkv
    assert nq == nkv * hper, (nq, nkv)
    # rows: chunk-major, heads-within-token minor -> row r = token r // hper
    qg = (q.reshape(b, c, nkv, hper, hd).transpose(0, 2, 1, 3, 4)
          .reshape(b, nkv, c * hper, hd))
    out = paged_attention_call(
        qg, k_pages, v_pages, k_scale, v_scale, page_table, kv_lengths,
        q_start, hper=hper, name="paged_prefill_attention",
        interpret=interpret)
    return (out.reshape(b, nkv, c, hper, hd).transpose(0, 2, 1, 3, 4)
            .reshape(b, c, nq, hd))
