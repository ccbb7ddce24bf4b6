"""Pallas TPU kernel: paged decode attention with inline int8 dequant.

Decode-shaped attention (q_len=1 per sequence) against the paged KV pool of
serving/kv_pool.py. The page table is a *scalar-prefetch* argument
(pltpu.PrefetchScalarGridSpec): BlockSpec index_maps read it to DMA the
right physical page for each (sequence, kv-head, page) grid step, so the
gather never materializes in HBM — pages stream HBM -> VMEM directly and
int8 pages are dequantized in-register against their per-(page, head)
scale. Online-softmax state (m, l, acc) lives in VMEM scratch across the
page axis, exactly like flash_attn.py's KV-block loop.

Grid: (B, n_kv_heads, n_pages) with pages innermost ("arbitrary" — the
accumulators carry across it). Each step DMAs one (page, hd) tile of the
head-major pool (kv_pool.py) and its (1, 1) scale. GQA query heads of one
KV head are processed together as a (hper, hd) block. Sequences shorter
than the page-table width mask dead slots by position; fully-dead pages are
skipped via pl.when (the DMA of the scratch page they point at is wasted
bandwidth, not wrong).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant.qtypes import unpack_int4_halves_lastdim
from repro.kernels import ops, ref, tpu_compiler_params

NEG_INF = -1e30


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page: int, scale: float,
            quantized: bool, packed: bool):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    klen = len_ref[b]

    @pl.when(j * page < klen)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (hper, hd)
        k = k_ref[0, 0]                                      # (page, hd[/2])
        v = v_ref[0, 0]
        if packed:                 # in-register nibble unpack: (page, hd)
            k = unpack_int4_halves_lastdim(k)
            v = unpack_int4_halves_lastdim(v)
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0, 0]                             # (1, 1) scale
            v = v * vs_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < klen, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, k_scale, v_scale,
                           page_table, kv_lengths, *,
                           interpret: bool = False):
    """q: (B, nq, hd); k_pages/v_pages: (P, nkv, page, hd) int8/float or
    (P, nkv, page, hd//2) uint8 packed int4; k_scale/v_scale: (P, nkv) f32
    (quantized pools) or None; page_table: (B, W) physical page ids;
    kv_lengths: (B,) valid keys (>= 1). Returns (B, nq, hd) in q.dtype."""
    b, nq, hd = q.shape
    n_pages, nkv, page, hd_kv = k_pages.shape      # hd_kv = hd//2 if packed
    w = page_table.shape[1]
    hper = nq // nkv
    assert nq == nkv * hper, (nq, nkv)
    k_scale, v_scale, quantized, packed = ops.paged_pool_scales(
        k_pages, k_scale, v_scale)

    qg = q.reshape(b, nkv, hper, hd)
    pt_flat = page_table.reshape(-1).astype(jnp.int32)

    kern = functools.partial(_kernel, page=page, scale=1.0 / (hd ** 0.5),
                             quantized=quantized, packed=packed)
    grid = (b, nkv, w)
    page_spec, scale_spec = ops.paged_block_specs(w, page, hd_kv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, hper, hd), lambda bi, h, j, pt, lens:
                         (bi, h, 0, 0)),
            page_spec,
            page_spec,
            scale_spec,
            scale_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, hper, hd), lambda bi, h, j, pt, lens:
                               (bi, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((hper, 1), jnp.float32),
                        pltpu.VMEM((hper, 1), jnp.float32),
                        pltpu.VMEM((hper, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, hper, hd), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(pt_flat, kv_lengths.astype(jnp.int32), qg, k_pages, v_pages,
      k_scale, v_scale)
    return out.reshape(b, nq, hd)


def paged_decode_attention_ref(q, k_pages, v_pages, k_scale, v_scale,
                               page_table, kv_lengths):
    """Pure-jnp oracle (and the XLA serving path on CPU): gather pages,
    dequantize, masked softmax. Same contract as the kernel."""
    b, nq, hd = q.shape
    nkv = k_pages.shape[1]
    hper = nq // nkv
    k = ref._read_pages(k_pages, k_scale, page_table)   # (B, W*page, nkv, hd)
    v = ref._read_pages(v_pages, v_scale, page_table)
    t = k.shape[1]
    # GQA via an explicit group axis: materializing jnp.repeat'ed K/V
    # costs ~2x the attention itself on the XLA CPU path; the grouped
    # contraction is bitwise identical (same per-(query, key) dot)
    qg = (q.reshape(b, nkv, hper, hd).astype(jnp.float32) / (hd ** 0.5))
    scores = jnp.einsum("bgph,btgh->bgpt", qg, k)
    mask = (jnp.arange(t)[None, None, None, :]
            < kv_lengths[:, None, None, None])
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgpt,btgh->bgph", probs, v)
    return out.reshape(b, nq, hd).astype(q.dtype)
