"""Representative quantized graphs for the dtype-flow checker.

Each builder returns a `TraceSpec` over the *real* production code paths:
the kernel ref oracles (the numerical contract the Pallas kernels are
pinned to), the jitted Pallas kernels themselves (structural int8-accum
check inside the kernel bodies), the PTQ-swapped transformer block, and the
paged-serving decode and mixed steps — the programs the continuous-
batching engine (`serving/engine.py`) jits and serves with.

Input tagging is automatic for pytree arguments (`auto_tags`): QTensor
leaves tag as quant data / per-channel scales, int8 pool pages as quant
data, uint8 pool pages as packed int4 data (the nibble pages of a
kv_bits=4 pool — the packed-int4-upcast invariant bites on them),
`k_s`/`v_s`/`*scale*` float leaves as scales.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.dtype_flow import TraceSpec
from repro.core.quant.qtypes import QTensor

_KEY_ENTRIES = jax.tree_util


def _key_name(key) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(key, attr):
            return str(getattr(key, attr))
    return str(key)


def _resolve(obj, keys):
    """Walk a key path (minus the final key) back into the original tree."""
    for k in keys:
        try:
            if hasattr(k, "key"):
                obj = obj[k.key]
            elif hasattr(k, "idx"):
                obj = obj[k.idx]
            elif hasattr(k, "name"):
                obj = getattr(obj, k.name)
            else:
                return None
        except Exception:
            return None
    return obj


def auto_tags(args: tuple, overrides: Dict[int, str] = None) -> Dict[int, str]:
    """Flat-leaf-index -> tag for the quant contract of `args`."""
    flat, _ = jax.tree_util.tree_flatten_with_path(args)
    tags: Dict[int, str] = {}
    for i, (path, leaf) in enumerate(flat):
        last = _key_name(path[-1]) if path else ""
        parent = _resolve(args, path[:-1]) if path else None
        if isinstance(parent, QTensor):
            if last == "data":
                tags[i] = "packed" if parent.is_packed else "quant"
            elif last == "scale":
                tags[i] = "scale"
            continue
        dtype = getattr(leaf, "dtype", None)
        if dtype == jnp.int8:
            tags[i] = "quant"
        elif dtype == jnp.uint8:
            tags[i] = "packed"       # nibble-packed int4 KV pages
        elif (dtype is not None and jnp.issubdtype(dtype, jnp.floating)
              and ("scale" in last or last in ("k_s", "v_s"))):
            tags[i] = "scale"
    if overrides:
        tags.update(overrides)
    return tags


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# Kernel-contract graphs
# ---------------------------------------------------------------------------

def spec_int8_gemm() -> TraceSpec:
    from repro.kernels import ref
    args = (_sds((16, 128), jnp.int8), _sds((128, 64), jnp.int8),
            _sds((16, 1), jnp.float32), _sds((1, 64), jnp.float32))
    return TraceSpec("int8_gemm", ref.int8_matmul_ref, args,
                     {0: "quant", 1: "quant", 2: "scale", 3: "scale"})


def spec_int8_gemm_kernel() -> TraceSpec:
    from repro.kernels import int8_gemm
    args = (_sds((32, 128), jnp.int8), _sds((128, 128), jnp.int8),
            _sds((32, 1), jnp.float32), _sds((1, 128), jnp.float32))
    return TraceSpec("int8_gemm_pallas",
                     partial(int8_gemm.int8_matmul, bm=32, bn=128, bk=128),
                     args, {0: "quant", 1: "quant", 2: "scale", 3: "scale"})


def spec_w4a8_gemm() -> TraceSpec:
    from repro.kernels import ref
    args = (_sds((8, 256), jnp.int8), _sds((128, 64), jnp.int8),
            _sds((8, 1), jnp.float32), _sds((2, 64), jnp.float32))
    return TraceSpec("w4a8_gemm",
                     partial(ref.w4a8_matmul_ref, group_size=128), args,
                     {0: "quant", 1: "packed", 2: "scale", 3: "scale"})


def spec_w4a8_gemm_kernel() -> TraceSpec:
    from repro.kernels import w4a8_gemm
    args = (_sds((32, 256), jnp.int8), _sds((128, 128), jnp.int8),
            _sds((32, 1), jnp.float32), _sds((2, 128), jnp.float32))
    return TraceSpec("w4a8_gemm_pallas",
                     partial(w4a8_gemm.w4a8_matmul, group_size=128,
                             bm=32, bn=128),
                     args, {0: "quant", 1: "packed", 2: "scale", 3: "scale"})


def spec_paged_attn_dequant() -> TraceSpec:
    from repro.kernels import paged_attn
    b, nq, nkv, hd, page, n_pages, w = 2, 4, 2, 32, 8, 5, 2
    args = (_sds((b, nq, hd), jnp.float32),
            _sds((n_pages, nkv, page, hd), jnp.int8),
            _sds((n_pages, nkv, page, hd), jnp.int8),
            _sds((n_pages, nkv), jnp.float32),
            _sds((n_pages, nkv), jnp.float32),
            _sds((b, w), jnp.int32), _sds((b,), jnp.int32))
    return TraceSpec("paged_attn_dequant",
                     paged_attn.paged_decode_attention_ref, args,
                     {1: "quant", 2: "quant", 3: "scale", 4: "scale"})


def spec_paged_prefill_dequant() -> TraceSpec:
    """The chunked-prefill attention oracle: chunk queries against int8
    pages, per-(page, head) dequant on the read path."""
    from repro.kernels import paged_prefill
    b, c, nq, nkv, hd, page, n_pages, w = 2, 16, 4, 2, 32, 8, 7, 3
    args = (_sds((b, c, nq, hd), jnp.float32),
            _sds((n_pages, nkv, page, hd), jnp.int8),
            _sds((n_pages, nkv, page, hd), jnp.int8),
            _sds((n_pages, nkv), jnp.float32),
            _sds((n_pages, nkv), jnp.float32),
            _sds((b, w), jnp.int32), _sds((b,), jnp.int32),
            _sds((b,), jnp.int32))
    return TraceSpec("paged_prefill_dequant",
                     paged_prefill.paged_prefill_attention_ref, args,
                     {1: "quant", 2: "quant", 3: "scale", 4: "scale"})


# ---------------------------------------------------------------------------
# Model-level graphs
# ---------------------------------------------------------------------------

def _tiny_ptq_model(qname: str = "int8"):
    from repro.configs import get_arch, reduced
    from repro.core.quant import calibrate, ptq
    from repro.core.quant.qtypes import preset
    from repro.models import transformer
    cfg = reduced(get_arch("pangu_1b"))
    qcfg = preset(qname)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.asarray(
        np.arange(2 * 8, dtype=np.int32).reshape(2, 8) % cfg.vocab)}
    stats = calibrate.collect_stats(params, [batch], cfg)
    pq = ptq.quantize_model(params, cfg, qcfg, stats)
    return cfg, qcfg, pq, batch


def spec_ptq_block(qname: str = "int8") -> TraceSpec:
    """The PTQ-swapped transformer block: quantize-act -> int GEMM ->
    dequant epilogue inside the scanned block stack (impl="xla")."""
    from repro.models import transformer
    cfg, qcfg, pq, batch = _tiny_ptq_model(qname)

    def fwd(params, batch):
        logits, _ = transformer.forward_train(params, batch, cfg, qcfg=qcfg,
                                              impl="xla", remat=False)
        return logits

    args = (pq, batch)
    return TraceSpec(f"ptq_block_{qname}", fwd, args, auto_tags(args))


def spec_serving_decode(kv_bits: int = 8) -> TraceSpec:
    """The paged serving decode step (the engine's `serve_decode`):
    int8 (or packed-int4 uint8, kv_bits=4) page pools + per-(page, head)
    scales through decode_step_paged — the int4 trace makes the
    packed-int4-never-upcast-before-shift invariant bite on serving."""
    from repro.configs import get_arch, reduced
    from repro.models import transformer
    cfg = reduced(get_arch("pangu_1b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    b, page, n_pages, w = 2, 8, 5, 2
    pools = transformer.init_paged_pools(cfg, n_pages, page, kv_bits=kv_bits)
    page_table = jnp.ones((b, w), jnp.int32)
    tokens = jnp.zeros((b,), jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)

    def step(params, pools, page_table, tokens, pos):
        logits, _ = transformer.decode_step_paged(
            params, pools, page_table, tokens, pos, cfg, paged_impl="xla")
        return logits

    name = ("serving_decode" if kv_bits == 8
            else f"serving_decode_int{kv_bits}")
    args = (params, pools, page_table, tokens, pos)
    return TraceSpec(name, step, args, auto_tags(args))


def _packed(b, c, q_start, n_new):
    """A mixed step's packed rows (tokens, pos, lane, grid) for `b` lanes
    of `c`-token chunks of token 0, on a row axis of b * c rows
    (`transformer.pack_rows`)."""
    from repro.models import transformer
    return tuple(jnp.asarray(a) for a in transformer.pack_rows(
        np.zeros((b, c), np.int32), q_start, n_new, b * c))


def spec_serving_prefill_chunk(kv_bits: int = 8) -> TraceSpec:
    """The chunked mixed prefill/decode step (the engine's `serve_mixed`):
    fused quantize-on-write into int8 (or
    packed-int4, kv_bits=4) pages — scale-once and int8-accum must hold
    through write_chunk's dequant -> merge -> requantize (for int4: the
    shift-unpack, then pack-on-store) as well as the attention read."""
    from repro.configs import get_arch, reduced
    from repro.models import transformer
    from repro.serving.kv_pool import chunk_window_pages
    cfg = reduced(get_arch("pangu_1b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    b, page, n_pages, w, c = 2, 8, 9, 3, 16
    wc = chunk_window_pages(c, page)
    pools = transformer.init_paged_pools(cfg, n_pages, page, kv_bits=kv_bits)
    page_table = jnp.ones((b, w), jnp.int32)
    window_rows = jnp.ones((b, wc), jnp.int32)
    q_start = jnp.zeros((b,), jnp.int32)
    n_new = jnp.full((b,), c, jnp.int32)
    rows = _packed(b, c, q_start, n_new)

    def step(params, pools, page_table, window_rows, tokens, pos, lane, grid,
             q_start, n_new):
        logits, _ = transformer.prefill_chunk_paged(
            params, pools, page_table, window_rows, tokens, pos, lane, grid,
            q_start, n_new, cfg, paged_impl="xla")
        return logits

    name = ("serving_prefill_chunk" if kv_bits == 8
            else f"serving_prefill_chunk_int{kv_bits}")
    args = (params, pools, page_table, window_rows, *rows, q_start, n_new)
    return TraceSpec(name, step, args, auto_tags(args))


def spec_serving_prefill_chunk_cached() -> TraceSpec:
    """The prefix-cache-hit mixed step: a prefill chunk whose page table
    maps previously-cached int8 pages for the shared prefix (q_start > 0,
    the chunk writes only fresh tail pages). Scale-once and int8-accum must
    hold when the attention read crosses pages this request never wrote —
    the cached pages' per-(page, head) scales travel with the page."""
    from repro.configs import get_arch, reduced
    from repro.models import transformer
    from repro.serving.kv_pool import chunk_window_pages
    cfg = reduced(get_arch("pangu_1b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    b, page, n_pages, w, c = 2, 8, 9, 4, 16
    wc = chunk_window_pages(c, page)
    pools = transformer.init_paged_pools(cfg, n_pages, page, kv_bits=8)
    # rows 1..2 are another request's cached prefix pages; rows 3.. are this
    # request's fresh tail pages — the write window starts past the hits
    page_table = jnp.asarray(
        np.arange(1, 1 + w, dtype=np.int32)[None].repeat(b, 0))
    window_rows = jnp.asarray(
        np.arange(3, 3 + wc, dtype=np.int32)[None].repeat(b, 0))
    q_start = jnp.full((b,), 2 * page, jnp.int32)    # 2 pages served by cache
    n_new = jnp.full((b,), c, jnp.int32)
    rows = _packed(b, c, q_start, n_new)

    def step(params, pools, page_table, window_rows, tokens, pos, lane, grid,
             q_start, n_new):
        logits, _ = transformer.prefill_chunk_paged(
            params, pools, page_table, window_rows, tokens, pos, lane, grid,
            q_start, n_new, cfg, paged_impl="xla")
        return logits

    args = (params, pools, page_table, window_rows, *rows, q_start, n_new)
    return TraceSpec("serving_prefill_chunk_cached", step, args,
                     auto_tags(args))


def spec_serving_verify_step() -> TraceSpec:
    """The speculative-verify step: a k+1-token draft window (unaligned —
    here C = 5) scored read-only by the multi-query chunk-attention read
    (raw window K/V spliced over the gathered int8 pages) against a page
    table holding pages this request never wrote (a cached/previously-
    committed prefix), then the accepted prefix committed through the
    fused quantize-on-write path. Each cached page's per-(page, head)
    scale must be applied exactly once on the read side, and the commit's
    requantization must keep int8 storage dtypes."""
    from repro.configs import get_arch, reduced
    from repro.models import transformer
    from repro.serving import kv_pool
    cfg = reduced(get_arch("pangu_1b"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    b, page, n_pages, w, c = 2, 8, 9, 4, 5
    wc = kv_pool.verify_window_pages(c, page)
    pools = transformer.init_paged_pools(cfg, n_pages, page, kv_bits=8)
    # rows 1..2 hold the committed prefix (written by earlier steps, so
    # the verify read never re-rounds them); the window starts mid-page 3
    page_table = jnp.asarray(
        np.arange(1, 1 + w, dtype=np.int32)[None].repeat(b, 0))
    window_rows = jnp.asarray(
        np.arange(3, 3 + wc, dtype=np.int32)[None].repeat(b, 0))
    tokens = jnp.zeros((b, c), jnp.int32)
    q_start = jnp.full((b,), 2 * page + 3, jnp.int32)   # unaligned start
    n_new = jnp.full((b,), c, jnp.int32)
    n_keep = jnp.full((b,), 3, jnp.int32)               # accept 2 + bonus

    def step(params, pools, page_table, window_rows, tokens, q_start,
             n_new, n_keep):
        logits, kv_win = transformer.verify_step_paged(
            params, pools, page_table, tokens, q_start, n_new, cfg)
        out = {}
        for i in pools:
            kw, vw = kv_win[i]
            out[i] = jax.vmap(kv_pool.write_chunk,
                              in_axes=(0, 0, 0, None, None, None))(
                pools[i], kw, vw, window_rows, q_start, n_keep)
        return logits, out

    args = (params, pools, page_table, window_rows, tokens, q_start,
            n_new, n_keep)
    return TraceSpec("serving_verify_step", step, args, auto_tags(args))


def spec_serving_mla_moe_step() -> TraceSpec:
    """openPangu-Ultra-MoE's mixed step (reduced widths): W8A8 latent
    attention writing int8 latent pages, a leading dense layer, and a layer
    of held experts whose sorted rows reach the grouped GEMM as int8 codes
    with per-row scales, beside a float32 router. Scale-once and int8-accum
    must hold through the latent pages and the grouped expert GEMM."""
    import dataclasses as dc
    from repro.configs import get_arch, reduced
    from repro.core.quant import ptq
    from repro.core.quant.qtypes import INT8
    from repro.models import transformer
    from repro.serving.kv_pool import chunk_window_pages
    cfg = reduced(get_arch("pangu_ultra_moe"))
    cfg = dc.replace(cfg, moe=dc.replace(cfg.moe, held=(0, 2)))
    params = ptq.quantize_model(
        transformer.init_params(jax.random.PRNGKey(0), cfg), cfg, INT8)
    b, page, n_pages, w, c = 2, 8, 9, 3, 16
    wc = chunk_window_pages(c, page)
    pools = transformer.init_paged_pools(cfg, n_pages, page, kv_bits=8)
    page_table = jnp.ones((b, w), jnp.int32)
    window_rows = jnp.ones((b, wc), jnp.int32)
    q_start = jnp.zeros((b,), jnp.int32)
    n_new = jnp.asarray([c, 1], jnp.int32)
    rows = _packed(b, c, q_start, n_new)

    def step(params, pools, page_table, window_rows, tokens, pos, lane, grid,
             q_start, n_new):
        logits, _, counters = transformer.prefill_chunk_paged(
            params, pools, page_table, window_rows, tokens, pos, lane, grid,
            q_start, n_new, cfg, qcfg=INT8, impl="xla", paged_impl="xla",
            with_stats=True)
        return logits, counters

    args = (params, pools, page_table, window_rows, *rows, q_start, n_new)
    return TraceSpec("serving_mla_moe_step", step, args, auto_tags(args))


def default_specs(*, fast: bool = False) -> List[TraceSpec]:
    specs = [
        spec_int8_gemm(),
        spec_int8_gemm_kernel(),
        spec_w4a8_gemm(),
        spec_w4a8_gemm_kernel(),
        spec_paged_attn_dequant(),
        spec_paged_prefill_dequant(),
    ]
    if not fast:
        specs.append(spec_ptq_block("int8"))
        specs.append(spec_ptq_block("w4a8"))
        specs.append(spec_serving_decode())
        specs.append(spec_serving_prefill_chunk())
        specs.append(spec_serving_prefill_chunk_cached())
        specs.append(spec_serving_verify_step())
        specs.append(spec_serving_decode(kv_bits=4))
        specs.append(spec_serving_prefill_chunk(kv_bits=4))
        specs.append(spec_serving_mla_moe_step())
    return specs
