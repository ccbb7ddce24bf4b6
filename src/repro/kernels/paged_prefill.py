"""Pallas TPU kernel: chunked-prefill attention over paged KV with inline
int8 dequant.

Chunk-shaped attention (q_len = C tokens per sequence) against the paged KV
pool of serving/kv_pool.py
— the read half of the fused quantize-on-write
prefill path: each chunk's K/V has already been quantized into its pages by
`kv_pool.write_chunk`, and this kernel attends causally over everything
written so far (earlier chunks + the in-flight chunk) without ever
materializing a dense cache.

Like paged_attn.py, the page table is a *scalar-prefetch* argument
(pltpu.PrefetchScalarGridSpec): BlockSpec index_maps read it to DMA the
right physical page per (sequence, kv-head, page) grid step, pages stream
HBM -> VMEM, and int8 pages are dequantized in-register against their
per-(page, head) scale. The differences from the decode kernel:

  * the query block is the whole chunk — GQA query heads fold into rows as
    (C * hper, hd), row r belonging to chunk token r // hper;
  * the mask is causal *within* the chunk: row r at absolute position
    q_start[b] + r // hper sees keys kpos <= that position (and
    kpos < kv_lengths[b], which covers slots riding the mixed step with
    fewer than C valid tokens — their extra rows attend a nonempty prefix
    and are discarded by the caller).

Online-softmax state (m, l, acc) lives in VMEM scratch across the page
axis, which is innermost ("arbitrary").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant.qtypes import unpack_int4_halves_lastdim
from repro.kernels import ops, tpu_compiler_params
from repro.kernels.ref import paged_prefill_attention_ref  # noqa: F401 oracle

NEG_INF = -1e30


def _kernel(pt_ref, qstart_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
            o_ref, m_ref, l_ref, acc_ref, *, page: int, hper: int,
            scale: float, quantized: bool, packed: bool):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    klen = len_ref[b]
    q0 = qstart_ref[b]

    @pl.when(j * page < klen)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # (C*hper, hd)
        k = k_ref[0, 0]                                  # (page, hd[/2])
        v = v_ref[0, 0]
        if packed:                 # in-register nibble unpack: (page, hd)
            k = unpack_int4_halves_lastdim(k)
            v = unpack_int4_halves_lastdim(v)
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0, 0]                         # (1, 1) scale
            v = v * vs_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // hper
        s = jnp.where((kpos <= qpos) & (kpos < klen), s, NEG_INF)
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
def paged_prefill_attention(q, k_pages, v_pages, k_scale, v_scale,
                            page_table, q_start, kv_lengths, *,
                            interpret: bool = False):
    """q: (B, C, nq, hd) chunk queries at positions q_start[b] + i;
    k_pages/v_pages: (P, nkv, page, hd) int8/float or (P, nkv, page, hd//2)
    uint8 packed int4; k_scale/v_scale: (P, nkv) f32 (quantized pools) or
    None; page_table: (B, W) physical ids; q_start: (B,); kv_lengths: (B,)
    valid keys (>= 1). Returns (B, C, nq, hd) in q.dtype. Same contract as
    `ref.paged_prefill_attention_ref`."""
    b, c, nq, hd = q.shape
    n_pages, nkv, page, hd_kv = k_pages.shape      # hd_kv = hd//2 if packed
    w = page_table.shape[1]
    hper = nq // nkv
    assert nq == nkv * hper, (nq, nkv)
    k_scale, v_scale, quantized, packed = ops.paged_pool_scales(
        k_pages, k_scale, v_scale)

    # rows: chunk-major, heads-within-token minor -> row r = token r // hper
    qg = (q.reshape(b, c, nkv, hper, hd).transpose(0, 2, 1, 3, 4)
          .reshape(b, nkv, c * hper, hd))
    pt_flat = page_table.reshape(-1).astype(jnp.int32)

    kern = functools.partial(_kernel, page=page, hper=hper,
                             scale=1.0 / (hd ** 0.5), quantized=quantized,
                             packed=packed)
    grid = (b, nkv, w)
    page_spec, scale_spec = ops.paged_block_specs(w, page, hd_kv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, c * hper, hd), lambda bi, h, j, pt, qs, lens:
                         (bi, h, 0, 0)),
            page_spec,
            page_spec,
            scale_spec,
            scale_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, c * hper, hd),
                               lambda bi, h, j, pt, qs, lens: (bi, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((c * hper, 1), jnp.float32),
                        pltpu.VMEM((c * hper, 1), jnp.float32),
                        pltpu.VMEM((c * hper, hd), jnp.float32)],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, c * hper, hd), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_prefill_attention",
    )(pt_flat, q_start.astype(jnp.int32), kv_lengths.astype(jnp.int32),
      qg, k_pages, v_pages, k_scale, v_scale)
    return (out.reshape(b, nkv, c, hper, hd).transpose(0, 2, 1, 3, 4)
            .reshape(b, c, nq, hd))

