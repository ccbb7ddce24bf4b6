"""Pallas TPU kernels: paged attention over the KV pool of serving/kv_pool.py
with inline int8 / packed-int4 dequant, and the page walk both share.

`paged_decode_attention` (q_len = 1 per lane) lives here;
`paged_prefill.paged_prefill_attention` (a chunk of queries per lane,
causal within the chunk) is the same walk with a causal mask. Each is one
`pallas_call` under its own `name=`.

The walk. The grid is one step per lane. Inside it a loop runs over the
lane's live compute blocks only: `ceil(kv_len / block_keys)` of them, each
of `block_pages(page, W)` pages (about BLOCK_KEYS keys). The pools stay in
HBM (`memory_space=pl.ANY`); each block's pages are copied by hand
(`pltpu.make_async_copy`), one DMA per page for K and one for V, each a
whole (n_kv_heads, page, hd) slab of the head-major pool, into one of two
VMEM slots: block j + 1 loads while block j computes. No copy is issued for
a page-table column at or past `ceil(kv_len / page)`; the slots of those
columns in a lane's last block are zeroed instead, so a dead column's page
is never read.

Per-(page, head) scales of a quantized pool reach the kernel gathered by
the lane's live page-table columns, (B, W, n_kv_heads) f32 in SMEM, one
lane's row per grid step; a page's rows are dequantized against that
scalar. A page whose stored head_dim is under 128 lanes (packed int4 at
head_dim 128) is copied as a lane-dense (page * hd_stored / 128, 128) view
of the same bytes, `g = 128 / hd_stored` tokens a row; the kernel lays the
g tokens of a row out as rows of g separate segments, so a block's key rows
are a fixed permutation of its positions, and the masks use the permuted
positions (`_key_positions`).

Arithmetic per block and KV head, as before: keys and values dequantized in
f32, f32 `dot_general`, online softmax (m, l, acc) in f32 VMEM scratch.
GQA query heads of one KV head are processed together as rows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant.qtypes import int4_nibbles
from repro.kernels import ref, tpu_compiler_params

NEG_INF = -1e30
LANE_WIDTH = 128   # a vreg row: the minor dim a DMA of a page must fill
# Keys per compute block: two MXU widths, enough that a block's DMAs cover
# its compute and few enough that a lane's last block wastes little.
BLOCK_KEYS = 256


def block_pages(page: int, w: int) -> int:
    """Pages per compute block: BLOCK_KEYS keys, at most the table's W."""
    return max(1, min(w, BLOCK_KEYS // page))


def pages_walked(kv_lengths, page: int):
    """Pages a lane's walk copies and computes: ceil(kv_len / page)."""
    return -(-kv_lengths // page)


def _stored_view(pages):
    """The pool as the kernel copies it: (P, nkv, page, hds) unchanged when
    hds fills whole 128-lane rows, else the same bytes as (P, nkv,
    page * hds / 128, 128). Returns (view, g tokens per row)."""
    n_pages, nkv, page, hds = pages.shape
    if hds % LANE_WIDTH == 0:
        return pages, 1
    row = LANE_WIDTH
    assert row % hds == 0 and (page * hds) % row == 0, pages.shape
    return pages.reshape(n_pages, nkv, page * hds // row, row), row // hds


def _page_rows(x, g: int, packed: bool):
    """One stored page of one head, (page * hds / 128 or page, row width),
    -> its (page, hd) key rows in f32: segment s of row r (token r*g + s)
    becomes row s * rows + r; packed bytes unpack to [low | high] nibbles."""
    hds = x.shape[1] // g
    if packed:
        lo, hi = int4_nibbles(x)
        segs = [jnp.concatenate([lo[:, s * hds:(s + 1) * hds],
                                 hi[:, s * hds:(s + 1) * hds]], axis=1)
                for s in range(g)]
    else:
        segs = [x[:, s * hds:(s + 1) * hds] for s in range(g)]
    rows = segs[0] if g == 1 else jnp.concatenate(segs, axis=0)
    return rows.astype(jnp.float32)


def _key_positions(blk, bk: int, page: int, g: int):
    """(1, bk) absolute positions of block `blk`'s key rows (the row order
    of `_page_rows`, page after page)."""
    x = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    within = x % page
    if g > 1:
        rows = page // g
        within = (within % rows) * g + within // rows
    return blk * bk + (x // page) * page + within


def _kernel(*refs, page: int, w: int, npg: int, g: int, hper: int,
            scale: float, causal: bool, quantized: bool, packed: bool):
    pt_ref, len_ref, *refs = refs
    qs_ref = refs.pop(0) if causal else None
    q_ref, k_hbm, v_hbm, *refs = refs
    ks_ref = vs_ref = None
    if quantized:
        ks_ref, vs_ref = refs.pop(0), refs.pop(0)
    o_ref, k_buf, v_buf, sem, m_ref, l_ref, acc_ref = refs

    b = pl.program_id(0)
    klen = len_ref[b]
    n_pages = jnp.minimum(pages_walked(klen, page), w)
    n_blocks = (n_pages + npg - 1) // npg
    nkv, rows = q_ref.shape[1], q_ref.shape[2]
    bk = npg * page

    def copies(blk, slot, i):
        pid = pt_ref[b * w + blk * npg + i]
        return [pltpu.make_async_copy(hbm.at[pid], buf.at[slot, i],
                                      sem.at[slot])
                for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf))]

    def start(blk, slot):
        for i in range(npg):
            @pl.when(blk * npg + i < n_pages)
            def _():
                for c in copies(blk, slot, i):
                    c.start()

    def wait(blk, slot):
        for i in range(npg):
            live = blk * npg + i < n_pages

            @pl.when(live)
            def _():
                for c in copies(blk, slot, i):
                    c.wait()

            # a NaN left in unset VMEM would survive p = 0 in p @ v
            @pl.when(jnp.logical_not(live))
            def _():
                k_buf[slot, i] = jnp.zeros(k_buf.shape[2:], k_buf.dtype)
                v_buf[slot, i] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)

    def block_rows(buf, s_ref, slot, blk, h):
        """(bk, hd) f32 keys or values of head h in slot's block."""
        pieces = []
        for i in range(npg):
            x = _page_rows(buf[slot, i, h], g, packed)
            if quantized:      # a dead slot is zeros: any column's scale
                x = x * s_ref[0, jnp.minimum(blk * npg + i, w - 1), h]
            pieces.append(x)
        return pieces[0] if npg == 1 else jnp.concatenate(pieces, axis=0)

    if causal:
        qpos = qs_ref[b] + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // hper

    def attend(blk, slot, h, live):
        q = q_ref[0, h].astype(jnp.float32) * scale          # (rows, hd)
        k = block_rows(k_buf, ks_ref, slot, blk, h)
        v = block_rows(v_buf, vs_ref, slot, blk, h)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_blocks > 0)
    def _():
        start(0, 0)

    def block(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            start(blk + 1, 1 - slot)

        wait(blk, slot)
        kpos = _key_positions(blk, bk, page, g)
        live = kpos < klen
        if causal:
            live = live & (kpos <= qpos)

        def head(h, c):
            attend(blk, slot, h, live)
            return c

        return jax.lax.fori_loop(0, nkv, head, carry)

    jax.lax.fori_loop(0, n_blocks, block, 0)
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_call(qg, k_pages, v_pages, k_scale, v_scale, page_table,
                         kv_lengths, q_start: Optional[jax.Array], *,
                         hper: int, name: str, interpret: bool):
    """The shared walk as one pallas_call named `name`. qg: (B, nkv, rows,
    hd) query rows per KV head, row r at position q_start[b] + r // hper
    when q_start is given (causal), else every row sees all kv_len keys.
    Pools, scales, page_table and kv_lengths as in the kernels' wrappers.
    Returns (B, nkv, rows, hd) in qg.dtype."""
    b, nkv, rows, hd = qg.shape
    page = k_pages.shape[2]
    w = page_table.shape[1]
    quantized = k_pages.dtype in (jnp.int8, jnp.uint8)
    packed = k_pages.dtype == jnp.uint8
    k_view, g = _stored_view(k_pages)
    v_view, _ = _stored_view(v_pages)
    npg = block_pages(page, w)
    causal = q_start is not None
    kv_lengths = kv_lengths.astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)

    prefetch = [page_table.reshape(-1), kv_lengths]
    if causal:
        prefetch.append(q_start.astype(jnp.int32))
    lane = lambda bi, *_: (bi, 0, 0, 0)               # noqa: E731
    in_specs = [pl.BlockSpec((1, nkv, rows, hd), lane),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [qg, k_view, v_view]
    if quantized:
        # each lane's scales, gathered by its live columns only (dead ones
        # repeat column 0), so no dead page's scale is read either
        live = (jnp.arange(w)[None, :]
                < pages_walked(kv_lengths, page)[:, None])
        cols = jnp.where(live, page_table, page_table[:, :1])
        scale_spec = pl.BlockSpec((1, w, nkv), lambda bi, *_: (bi, 0, 0),
                                  memory_space=pltpu.SMEM)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale[cols], v_scale[cols]]

    kern = functools.partial(
        _kernel, page=page, w=w, npg=npg, g=g, hper=hper,
        scale=1.0 / (hd ** 0.5), causal=causal, quantized=quantized,
        packed=packed)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nkv, rows, hd), lane),
        scratch_shapes=[pltpu.VMEM((2, npg) + k_view.shape[1:], k_view.dtype),
                        pltpu.VMEM((2, npg) + v_view.shape[1:], v_view.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((nkv, rows, 1), jnp.float32),
                        pltpu.VMEM((nkv, rows, 1), jnp.float32),
                        pltpu.VMEM((nkv, rows, hd), jnp.float32)],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, rows, hd), qg.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=name,
    )(*prefetch, *operands)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, k_scale, v_scale,
                           page_table, kv_lengths, *,
                           interpret: bool = False):
    """q: (B, nq, hd); k_pages/v_pages: (P, nkv, page, hd) int8/float or
    (P, nkv, page, hd//2) uint8 packed int4; k_scale/v_scale: (P, nkv) f32
    (quantized pools) or None; page_table: (B, W) physical page ids;
    kv_lengths: (B,) valid keys (>= 1, at most W * page). Returns
    (B, nq, hd) in q.dtype."""
    b, nq, hd = q.shape
    nkv = k_pages.shape[1]
    hper = nq // nkv
    assert nq == nkv * hper, (nq, nkv)
    out = paged_attention_call(
        q.reshape(b, nkv, hper, hd), k_pages, v_pages, k_scale, v_scale,
        page_table, kv_lengths, None, hper=hper,
        name="paged_decode_attention", interpret=interpret)
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
