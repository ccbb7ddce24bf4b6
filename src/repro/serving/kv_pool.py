"""Paged KV cache: a fixed pool of fixed-size pages with per-page int8 or
packed-int4 quantization (per-head scales) and free-list reuse.

Layout per transformer block (leading layer axis L added by
`transformer.init_paged_pools`; inside a step program the layer scan views
a stack as one pool of L*N pages, layer l's page p at l*N + p, and every
helper below serves that view as it serves one layer's pool):

    k, v   : (P, n_kv_heads, page_size, head_dim)      int8 | cache dtype
             (P, n_kv_heads, page_size, head_dim // 2) uint8, kv_bits=4:
             two nibbles per byte along head_dim in the grouped-halves
             layout (`qtypes.pack_int4_halves_lastdim`)
    k_s,v_s: (P, n_kv_heads) float32                   (quantized pools)

Pages are head-major: one (page_size, head_dim) tile per (page, kv-head),
so the paged Pallas kernels block a page as (1, 1, page_size, head_dim) —
the last two block dims equal the array's, which the TPU (8, 128) block
rule accepts for any page size. Token-major pages would put a 1 on the
n_kv_heads axis, second-to-last in the block, which Mosaic rejects.
Writes and oracle reads do their token-wise math on (page, nkv, hd) views
and swap the two axes at the storage boundary.

A latent-attention model (`cfg.mla`) keeps one latent row per token and
layer instead of keys and values: a K-only pool with one "head" as wide as
the latent ([c_kv | k_rope], `mla.latent_dim`, zero-padded to
`mla.pool_width`), and no `v` / `v_s` leaves.
Every write and read below carries over: each loops over the leaves the
pool holds, so the bf16 / int8 / packed-int4 layouts, the running-absmax
page writes, truncation and the prefix cache serve it unchanged, with one
scale per page over the whole latent row.

The leaf dtype is the discriminator — uint8 means packed int4, int8 means
int8, floats mean an unquantized pool — so kernels and oracles that only
see bare arrays can pick the right read path without any config plumbing.

Physical page 0 is reserved as the *scratch page*: unassigned page-table
entries point at it, so every gather/scatter stays shape-static and
branch-free — writes to it are garbage sinks, reads from it are masked by
`kv_lengths`. The host-side `PageAllocator` hands out pages 1..P-1.

Quantization is per (page, kv-head): one f32 scale covers page_size tokens,
so the scale overhead amortizes to 4/page_size bytes per token per head —
the int8 pool lands at ~50% of the bf16 pool's bytes/token instead of the
~56% a per-token-scale layout costs at small head_dim. Decode writes land
one token at a time: the target page is gathered, dequantized, masked to
the tokens actually written so far (freed pages are reused without
zeroing), extended, and requantized against the updated per-head absmax.
That re-rounding is bounded by the final page scale and touches only
page_size tokens per step — O(page) work against the attention's O(T).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant.qtypes import (pack_int4_halves_lastdim, paper_scale,
                                     qmax, qmin, unpack_int4_halves_lastdim)

SCRATCH_PAGE = 0


# ---------------------------------------------------------------------------
# Host-side allocator
# ---------------------------------------------------------------------------

class PageAllocator:
    """Refcounted free-list over physical pages 1..n_pages-1 (page 0 is
    scratch). Pages allocate at refcount 1; `incref` shares a page across
    page tables (prefix-cache hits), and `free` decrements one holder —
    the page returns to the free list only when its last holder releases.

    `free` keeps the hardening against the two scheduler bugs that silently
    corrupt a shared pool: double-free (a zero-refcount page re-enters the
    free list while a sequence still maps it -> cross-sequence KV leakage)
    and out-of-range ids (a stale page table row scattering into foreign
    memory).

    A page dropping to refcount 0 is offered to `reclaim_hook` (set by the
    prefix cache): if the hook claims it, the page is *parked* — neither
    live nor allocatable — until `adopt` re-references it (a cache hit on a
    cold page) or `reclaim` returns it to the free list (cache eviction).
    """

    def __init__(self, n_pages: int):
        assert n_pages >= 2, "need at least one allocatable page + scratch"
        self.n_pages = n_pages
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._ref: Dict[int, int] = {}
        self._parked: set = set()
        self.reclaim_hook: Optional[Callable[[int], bool]] = None

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_live(self) -> int:
        return len(self._ref)

    @property
    def n_parked(self) -> int:
        return len(self._parked)

    def refcount(self, page: int) -> int:
        return self._ref.get(int(page), 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n pages at refcount 1 or None (all-or-nothing; no partials)."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._ref[p] = 1
        return out

    def incref(self, page: int) -> None:
        """Add a holder to a live page (sharing an existing mapping)."""
        p = int(page)
        assert p in self._ref, f"incref of unallocated page {p}"
        self._ref[p] += 1

    def free(self, pages) -> None:
        """Release one holder per listed page."""
        for p in pages:
            p = int(p)
            assert p != SCRATCH_PAGE, "freeing the scratch page"
            assert 0 < p < self.n_pages, f"page id {p} out of range " \
                f"[1, {self.n_pages - 1}]"
            assert p in self._ref, f"double free of page {p}"
            self._ref[p] -= 1
            if self._ref[p] == 0:
                del self._ref[p]
                if self.reclaim_hook is not None and self.reclaim_hook(p):
                    self._parked.add(p)
                else:
                    self._free.append(p)

    def adopt(self, page: int) -> None:
        """Re-reference a parked page (prefix-cache hit on a cold page)."""
        p = int(page)
        assert p in self._parked, f"adopt of unparked page {p}"
        self._parked.discard(p)
        self._ref[p] = 1

    def reclaim(self, page: int) -> None:
        """Return a parked page to the free list (prefix-cache eviction)."""
        p = int(page)
        assert p in self._parked, f"reclaim of unparked page {p}"
        self._parked.discard(p)
        self._free.append(p)


# ---------------------------------------------------------------------------
# Device-side pool (no layer axis: one layer's pages, or a stack's flat view)
# ---------------------------------------------------------------------------

def init_pool(cfg, n_pages: int, page_size: int, kv_bits: int = 16,
              dtype=jnp.bfloat16) -> dict:
    assert kv_bits in (16, 8, 4), f"unsupported kv_bits {kv_bits}"
    mla = getattr(cfg, "mla", None)
    if mla is not None:         # K-only: one latent row per token
        nkv, hd, names = 1, mla.pool_width, ("k",)
    else:
        nkv, hd, names = cfg.n_kv_heads, cfg.hd, ("k", "v")
    shape = (n_pages, nkv, page_size, hd)
    scales = {f"{n}_s": jnp.zeros((n_pages, nkv), jnp.float32)
              for n in names}
    if kv_bits == 8:
        return {**{n: jnp.zeros(shape, jnp.int8) for n in names}, **scales}
    if kv_bits == 4:
        assert hd % 2 == 0, f"head_dim {hd} must be even for packed int4"
        pshape = (n_pages, nkv, page_size, hd // 2)
        return {**{n: jnp.zeros(pshape, jnp.uint8) for n in names},
                **scales}
    return {n: jnp.zeros(shape, dtype) for n in names}


def _leaves(pool: dict, k, v):
    """(leaf, scale leaf, new rows) of the leaves the pool holds: keys and
    values, or the latent alone (a K-only pool, whose `v` is None)."""
    out = [("k", "k_s", k)]
    if "v" in pool:
        out.append(("v", "v_s", v))
    return out


def pool_is_quantized(pool: dict) -> bool:
    return pool["k"].dtype in (jnp.int8, jnp.uint8)


def page_size_of(pool: dict) -> int:
    return pool["k"].shape[-2]


def pool_kv_bits(pool: dict) -> int:
    """Recover kv_bits from the leaf dtype (uint8 = packed int4)."""
    dt = pool["k"].dtype
    if dt == jnp.uint8:
        return 4
    return 8 if dt == jnp.int8 else 16


def pool_bytes(pool: dict) -> int:
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(pool))


def bytes_per_token(pool: dict) -> float:
    """Pool bytes per token *slot* (both K and V, incl. scale overhead)."""
    return pool_bytes(pool) / (pool["k"].shape[0] * page_size_of(pool))


def _quantize_pages(x: jax.Array, bits: int = 8):
    """x: (..., nkv, page, hd) head-major pages -> (quantized pages, per
    (page, head) scale (..., nkv)).

    bits=8 yields int8 codes; bits=4 narrow-clips to [-7, 7] and packs two
    nibbles per byte along head_dim (uint8, grouped halves) so no dense
    intermediate wider than the packed page ever lands in the pool.
    """
    am = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-2, -1))   # (..., nkv)
    s = paper_scale(am, bits)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None, None]),
                 qmin(bits), qmax(bits)).astype(jnp.int8)
    if bits == 4:
        return pack_int4_halves_lastdim(q), s
    return q, s


def _to_pages(x: jax.Array, page: int) -> jax.Array:
    """Token-major (B, W*page, nkv, hd) -> head-major pages
    (B*W, nkv, page, hd), ready to scatter at flat page ids."""
    b, t, nkv, hd = x.shape
    return (x.reshape(b, t // page, page, nkv, hd).swapaxes(2, 3)
            .reshape(-1, nkv, page, hd))


def dequant_pages(pages, scales):
    """Gathered head-major pages (B, W, nkv, page, hd[/2]) -> token-major
    f32 (B, W*page, nkv, hd): nibble-unpack uint8 (packed int4) pages, apply
    the per-(page, head) scales (B, W, nkv) when given (quantized pools;
    None for float pools), and swap to token order. The one read path of
    the pool's merge-on-write, `gather_kv` and the kernels' oracles."""
    if pages.dtype == jnp.uint8:
        pages = unpack_int4_halves_lastdim(pages)
    f = pages.astype(jnp.float32)
    if scales is not None:
        f = f * scales[..., None, None]
    b, w, nkv, page, hd = f.shape
    return f.swapaxes(2, 3).reshape(b, w * page, nkv, hd)


def _store_pages(pool: dict, name: str, s_name: str, ids: jax.Array,
                 pages: jax.Array, bits: int) -> None:
    """Quantize (quantized pools) and scatter head-major f32 pages
    (N, nkv, page, hd) into pool[name] at physical ids (N,)."""
    if pool_is_quantized(pool):
        q, sc = _quantize_pages(pages, bits)
        pool[name] = pool[name].at[ids].set(q)
        pool[s_name] = pool[s_name].at[ids].set(sc)
    else:
        pool[name] = pool[name].at[ids].set(pages.astype(pool[name].dtype))


# -- prefill: bulk page fill -------------------------------------------------

def write_prefill(pool: dict, k: jax.Array, v: jax.Array,
                  page_rows: jax.Array, lengths: jax.Array) -> dict:
    """Scatter a prompt's K/V into its pages.

    k, v: (B, S, nkv, hd) with S % page_size == 0 (prompt bucket);
    page_rows: (B, S // page_size) physical ids (scratch-padded);
    lengths: (B,) valid prompt tokens — positions beyond are zeroed so they
    can't inflate the page scale.
    """
    page = page_size_of(pool)
    s = k.shape[1]
    assert s % page == 0, (s, page)
    valid = (jnp.arange(s)[None, :] < lengths[:, None])[..., None, None]
    ids = page_rows.reshape(-1)
    bits = pool_kv_bits(pool)
    pool = dict(pool)
    for name, s_name, x in _leaves(pool, k, v):
        pages = _to_pages(jnp.where(valid, x, 0).astype(jnp.float32), page)
        _store_pages(pool, name, s_name, ids, pages, bits)
    return pool


# -- chunked prefill: fused quantize-on-write ---------------------------------

def chunk_window_pages(chunk_tokens: int, page_size: int) -> int:
    """Pages a C-token write window can span at arbitrary (unaligned) start:
    C//page full pages plus one boundary page."""
    assert chunk_tokens % page_size == 0, (chunk_tokens, page_size)
    return chunk_tokens // page_size + 1


def write_chunk(pool: dict, k: jax.Array, v: jax.Array,
                window_rows: jax.Array, start: jax.Array,
                n_new: jax.Array, src: Optional[dict] = None) -> dict:
    """Write up to C new tokens per sequence at positions start..start+n_new-1,
    quantizing directly into pages (no dense intermediate cache).

    k, v: (B, C, nkv, hd) chunk K/V (positions beyond n_new are garbage);
    window_rows: (B, Wc) physical page ids covering page indices
    start//page .. start//page + Wc - 1 (scratch beyond the sequence's
    allocation), Wc = chunk_window_pages(C, page);
    start: (B,) absolute position of chunk token 0 (== tokens already in
    cache); n_new: (B,) valid tokens this step — C for a full prefill chunk,
    1 for a riding decode slot, 0 for an idle slot.

    Boundary pages are gathered, dequantized, masked to their previously
    written tokens (positions < start; freed pages are reused without
    zeroing), merged with the chunk, and requantized per (page, head) —
    the same bounded re-rounding `write_token` pays, amortized over the
    whole chunk. Unwritten window positions are zeroed so they cannot
    inflate the page scale.

    src: optional pre-gathered window leaves {leaf: (B, Wc, ...)} to merge
    against instead of gathering pool[leaf][window_rows] — `truncate` uses
    this to rebuild the window from its pre-speculation snapshot without a
    restore scatter + re-gather round trip.
    """
    page = page_size_of(pool)
    b, c = k.shape[:2]
    wc = window_rows.shape[1]
    assert wc * page >= c + page, (wc, page, c)
    wpos = jnp.arange(wc * page)[None, :]                     # window-local
    base = (start // page) * page
    gpos = base[:, None] + wpos                               # absolute
    off = start - base                                        # (B,)
    j = wpos - off[:, None]                                   # chunk index
    jc = jnp.clip(j, 0, c - 1)
    keep_old = (gpos < start[:, None])[..., None, None]
    use_new = ((j >= 0) & (j < n_new[:, None]))[..., None, None]
    ids = window_rows.reshape(-1)
    quantized = pool_is_quantized(pool)
    bits = pool_kv_bits(pool)
    pool = dict(pool)
    for name, s_name, tok in _leaves(pool, k, v):
        leaves = src if src is not None else {
            n: pool[n][window_rows] for n in (name, s_name) if n in pool}
        f = dequant_pages(leaves[name],
                          leaves[s_name] if quantized else None)
        f = jnp.where(keep_old, f, 0.0)               # (B, Wc*page, nkv, hd)
        newv = jnp.take_along_axis(tok.astype(jnp.float32),
                                   jc[:, :, None, None], axis=1)
        f = jnp.where(use_new, newv, f)
        _store_pages(pool, name, s_name, ids, _to_pages(f, page), bits)
    return pool


# -- speculative verify: page-exact rollback ---------------------------------

def verify_window_pages(chunk_tokens: int, page_size: int) -> int:
    """Pages a C-token verify window can span at arbitrary start. Unlike
    `chunk_window_pages` the window length (k+1 draft tokens) need not be
    page-aligned, so this is ceil(C/page) full-or-partial pages plus one
    boundary page — sized to satisfy write_chunk's Wc*page >= C + page."""
    return -(-chunk_tokens // page_size) + 1


def truncate(pool: dict, window_rows: jax.Array, snap: dict, k: jax.Array,
             v: jax.Array, start: jax.Array, n_keep: jax.Array) -> dict:
    """Roll a speculative window back page-exactly, keeping only the
    accepted prefix.

    `snap` holds the window pages as they were *before* the verify write
    (one leaf per pool leaf, shaped (B, Wc, ...) — a `pool[leaf][window_rows]`
    gather). Re-running `write_chunk` against the snapshot (src=snap, so
    the post-verify page contents never enter the merge) with
    n_keep <= n_new makes the final pool bit-identical to having written
    only the accepted tokens in the first place: rewriting on top of the
    post-verify pages instead would pay an extra dequant-requant round
    trip on the boundary page and drift from the vanilla chain.

    n_keep: (B,) tokens to commit (accepted + bonus; 0 for idle lanes).
    """
    return write_chunk(pool, k, v, window_rows, start, n_keep, src=snap)


# -- decode: one token per sequence ------------------------------------------

def _requant_page(pages_f, new_tok, slot, bits=8):
    """pages_f: (B, nkv, page, hd) f32 (already dequantized + masked);
    new_tok: (B, nkv, hd); slot: (B,) write slot. Returns (q, scale)."""
    b = pages_f.shape[0]
    pages_f = pages_f.at[jnp.arange(b), :, slot].set(
        new_tok.astype(jnp.float32))
    return _quantize_pages(pages_f, bits)


def write_token(pool: dict, page_table: jax.Array, pos: jax.Array,
                k: jax.Array, v: jax.Array) -> dict:
    """Write one token per sequence at absolute position `pos` (B,).

    page_table: (B, W) physical ids; k, v: (B, nkv, hd). Inactive slots
    should carry pos=0 with a scratch-zeroed page-table row.
    """
    page = page_size_of(pool)
    b = k.shape[0]
    pidx = pos // page
    slot = pos % page
    phys = page_table[jnp.arange(b), pidx]                      # (B,)
    pool = dict(pool)
    if pool_is_quantized(pool):
        # Gather page, dequantize, zero not-yet-written slots (pages are
        # reused without zeroing), extend, requantize per (page, head).
        bits = pool_kv_bits(pool)
        live = jnp.arange(page)[None, None, :, None] <= slot[:, None, None,
                                                            None]
        for name, s_name, tok in _leaves(pool, k, v):
            pg = pool[name][phys]                               # (B,nkv,page,.)
            if bits == 4:
                pg = unpack_int4_halves_lastdim(pg)
            pg = pg.astype(jnp.float32)
            sc = pool[s_name][phys]                             # (B,nkv)
            pg = jnp.where(live, pg * sc[:, :, None, None], 0.0)
            q, s_new = _requant_page(pg, tok, slot, bits)
            pool[name] = pool[name].at[phys].set(q)
            pool[s_name] = pool[s_name].at[phys].set(s_new)
    else:
        for name, _, tok in _leaves(pool, k, v):
            pool[name] = pool[name].at[phys, :, slot].set(
                tok.astype(pool[name].dtype))
    return pool


# -- reads -------------------------------------------------------------------

def gather_kv(pool: dict, page_table: jax.Array):
    """Dequantized gather: (B, W*page, nkv, hd) bf16 pair — the XLA
    reference read path (the Pallas kernel streams pages instead). A
    K-only (latent) pool gives (latent, None)."""
    out = [None, None]
    for j, (name, s_name, _) in enumerate(_leaves(pool, None, None)):
        sc = pool[s_name][page_table] if pool_is_quantized(pool) else None
        out[j] = (dequant_pages(pool[name][page_table], sc)
                  .astype(jnp.bfloat16))
    return out[0], out[1]
