"""Attention: GQA / MQA, sliding-window, qk-norm, QKV-bias, cross-attention,
with dense / rolling-window / int8-quantized KV caches.

Projections are fused ([q|k|v] one GEMM) and quantization-aware. Scores and
softmax run in float32; grouped einsums avoid materializing repeated KV
heads. Rolling-window caches (Mixtral SWA) keep `window` slots and recover
absolute key positions arithmetically from the decode position, which is a
per-request vector (continuous batching).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.quant import qlinear
from repro.core.quant.qtypes import QuantConfig, paper_scale, qmax, qmin
from repro.models.layers import Taps, apply_rope, rms_norm

NEG_INF = -1e9


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(key, cfg, cross: bool = False) -> dict:
    if cfg.mla is not None and not cross:
        return init_mla(key, cfg)
    d, hd = cfg.d_model, cfg.hd
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 3)
    p = {}
    if cross:
        p["wq"] = qlinear.init_linear(ks[0], d, nq * hd, bias=cfg.qkv_bias)
        p["wkv"] = qlinear.init_linear(ks[1], d, 2 * nkv * hd, bias=cfg.qkv_bias)
    else:
        p["wqkv"] = qlinear.init_linear(ks[0], d, (nq + 2 * nkv) * hd,
                                        bias=cfg.qkv_bias)
    p["wo"] = qlinear.init_linear(ks[2], nq * hd, d)
    if cfg.qk_norm:
        p["qnorm"] = {"g": jnp.ones((hd,), jnp.float32)}
        p["knorm"] = {"g": jnp.ones((hd,), jnp.float32)}
    return p


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _qkv(p, x, cfg, positions, qcfg, impl, taps, tap_prefix, ctx=None,
         ctx_positions=None):
    """Project to q (B,S,Hq,hd), k/v (B,T,G,hd) with qk-norm + RoPE applied."""
    nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    if ctx is None:
        if taps is not None:
            taps.record(tap_prefix + "attn_in", x)
        qkv = qlinear.apply(p["wqkv"], x, qcfg, impl)
        q, k, v = jnp.split(qkv, [nq * hd, (nq + nkv) * hd], axis=-1)
        k_positions = positions
    else:
        if taps is not None:
            taps.record(tap_prefix + "attn_in", x)
            taps.record(tap_prefix + "attn_ctx_in", ctx)
        q = qlinear.apply(p["wq"], x, qcfg, impl)
        kv = qlinear.apply(p["wkv"], ctx, qcfg, impl)
        k, v = jnp.split(kv, 2, axis=-1)
        k_positions = ctx_positions
    q = _split_heads(q, nq, hd)
    k = _split_heads(k, nkv, hd)
    v = _split_heads(v, nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["qnorm"]["g"], cfg.norm_eps)
        k = rms_norm(k, p["knorm"]["g"], cfg.norm_eps)
    if positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
    if k_positions is not None:
        k = apply_rope(k, k_positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, cfg):
    """q (B,S,Hq,hd); k,v (B,T,G,hd); mask broadcastable to (B,H,S,T).

    GQA KV heads are broadcast up to the full head count *at use*: the
    grouped (G, H/G) einsum form defeats GSPMD head-sharding whenever
    n_kv < the model-axis size (the 5-D reshape has no shardable head dim),
    which replicated 34 GB of scores in the 90B dry-run. The repeat is a
    broadcast XLA folds into the einsum; caches stay at n_kv heads."""
    b, s = q.shape[0], q.shape[1]
    nq, nkv = q.shape[2], k.shape[2]
    if nq != nkv and _GQA_GROUPED and (nq // nkv) % 16 == 0:
        # §Perf lever: grouped form keeps K/V at n_kv heads through the
        # score dot (no 16x K-read inflation for kv=2 archs like glm4);
        # only safe when the per-group head dim still shards (hper % 16).
        return _sdpa_grouped(q, k, v, mask)
    if nq != nkv:
        k = jnp.repeat(k, nq // nkv, axis=2)
        v = jnp.repeat(v, nq // nkv, axis=2)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    # bf16 operands, f32 accumulation (MXU-native) — casting K/V to f32
    # up-front would double the gathered-KV footprint at 32k decode.
    scores = jnp.einsum("bshd,bthd->bhst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if _SCORES_BF16:
        scores = scores.astype(jnp.bfloat16)
        scores = jnp.where(mask, scores, jnp.bfloat16(NEG_INF))
    else:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, -1).astype(q.dtype)


def _sdpa_grouped(q, k, v, mask):
    """GQA without KV repeat: (B,S,G,Hper,hd) x (B,T,G,hd)."""
    b, s = q.shape[0], q.shape[1]
    nkv = k.shape[2]
    hper = q.shape[2] // nkv
    qg = q.reshape(b, s, nkv, hper, q.shape[-1])
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.einsum("bsghd,btgd->bghst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask,
                       scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bghst,btgd->bsghd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, -1).astype(q.dtype)


_GQA_GROUPED = bool(int(_os.environ.get("REPRO_GQA_GROUPED", "0")))     if "_os" in dir() else False

# Global score-element budget per attention chunk (f32 elements across the
# whole mesh); queries are processed in chunks beyond it (exact — each query
# row sees all its keys, no online-softmax needed). The HBM-conscious
# stand-in for a flash kernel at 32k prefill / 4k training. Bigger chunks
# cut the per-chunk K/V re-read traffic proportionally (a §Perf lever);
# override with REPRO_SCORE_BUDGET_LOG2.
import os as _os
_SCORE_BUDGET = 1 << int(_os.environ.get("REPRO_SCORE_BUDGET_LOG2", "31"))
_GQA_GROUPED = bool(int(_os.environ.get("REPRO_GQA_GROUPED", "0")))
# §Perf lever: store the masked scores/probs in bf16 (softmax still
# max-subtracted). Halves the dominant HBM traffic of XLA-lowered
# attention at 32k; a fused flash kernel removes it entirely.
_SCORES_BF16 = bool(int(_os.environ.get("REPRO_SCORES_BF16", "0")))
# Deployment flag: route causal attention through the Pallas flash kernel.
_USE_FLASH = bool(int(_os.environ.get("REPRO_FLASH", "0")))


def sdpa_causal(q, k, v, cfg, *, window: int = 0, lengths=None,
                t_offset: int = 0):
    """Query-chunked exact causal attention."""
    b, s = q.shape[0], q.shape[1]
    h = q.shape[2]
    t = k.shape[1]
    if _USE_FLASH and lengths is None and t_offset == 0 \
            and q.shape[-1] % 8 == 0:
        # Deployment path: the Pallas flash kernel (scores never touch
        # HBM), resolved like every kernel: on TPU under "auto", the XLA
        # path below elsewhere.
        from repro.kernels import ops
        from repro.kernels.flash_attn import flash_attention
        if ops.resolve_impl(None) == "pallas":
            return flash_attention(q, k, v, causal=True,
                                   window=window).reshape(b, s, -1)
    if b * h * s * t <= _SCORE_BUDGET:
        mask = causal_mask(s, t_offset=t_offset, window=window,
                           lengths=lengths, t=t)
        return _sdpa(q, k, v, mask, cfg)
    qc = max(128, _SCORE_BUDGET // (b * h * t))
    while s % qc:
        qc //= 2
    nc = s // qc
    qs = q.reshape(b, nc, qc, *q.shape[2:]).swapaxes(0, 1)   # (nc,B,qc,H,hd)
    offsets = jnp.arange(nc) * qc + t_offset

    @jax.checkpoint
    def body(_, inp):
        qi, off = inp
        mask = causal_mask(qc, t_offset=off, window=window,
                           lengths=lengths, t=t)
        return (), _sdpa(qi, k, v, mask, cfg)

    _, outs = jax.lax.scan(body, (), (qs, offsets))
    return outs.swapaxes(0, 1).reshape(b, s, -1)


def causal_mask(s: int, t_offset: int = 0, window: int = 0,
                lengths: Optional[jax.Array] = None, t: Optional[int] = None):
    """(1|B, 1, S, T) boolean mask. t_offset: absolute position of query 0
    relative to key 0 (for chunked prefill)."""
    t = t if t is not None else s
    qpos = jnp.arange(s)[:, None] + t_offset
    kpos = jnp.arange(t)[None, :]
    m = kpos <= qpos
    if window:
        m &= kpos > qpos - window
    m = m[None, None]
    if lengths is not None:
        keyvalid = jnp.arange(t)[None, :] < lengths[:, None]   # (B, T)
        m = m & keyvalid[:, None, None, :]
    return m


# ---------------------------------------------------------------------------
# Train / prefill forward (no cache reads; returns k/v for cache build)
# ---------------------------------------------------------------------------

def attn_forward(p, x, cfg, positions, *, ctx=None, ctx_positions=None,
                 lengths=None, qcfg: Optional[QuantConfig] = None,
                 impl=None, taps: Optional[Taps] = None, tap_prefix=""):
    if cfg.mla is not None and ctx is None:
        return mla_forward(p, x, cfg, positions, lengths=lengths, qcfg=qcfg,
                           impl=impl, taps=taps, tap_prefix=tap_prefix)
    q, k, v = _qkv(p, x, cfg, positions, qcfg, impl, taps, tap_prefix,
                   ctx=ctx, ctx_positions=ctx_positions)
    s = x.shape[1]
    if ctx is None:
        out = sdpa_causal(q, k, v, cfg, window=cfg.sliding_window,
                          lengths=lengths)
    else:  # cross-attn: all context visible (context lengths assumed full)
        mask = jnp.ones((1, 1, s, ctx.shape[1]), bool)
        out = _sdpa(q, k, v, mask, cfg)
    if taps is not None:
        taps.record(tap_prefix + "attn_out", out)
    out = qlinear.apply(p["wo"], out, qcfg, impl)
    return out, (k, v)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def init_kv_cache(cfg, batch: int, max_len: int, kv_bits: int = 16,
                  dtype=jnp.bfloat16) -> dict:
    """Dense or rolling-window cache. kv_bits == 8 stores int8 + scales
    (beyond-paper KV quantization)."""
    window = cfg.sliding_window
    size = min(window, max_len) if window else max_len
    nkv, hd = cfg.n_kv_heads, cfg.hd
    shape = (batch, size, nkv, hd)
    c = {}
    if kv_bits == 8:
        c["k"] = jnp.zeros(shape, jnp.int8)
        c["v"] = jnp.zeros(shape, jnp.int8)
        c["k_s"] = jnp.zeros((batch, size, nkv, 1), jnp.float32)
        c["v_s"] = jnp.zeros((batch, size, nkv, 1), jnp.float32)
    else:
        c["k"] = jnp.zeros(shape, dtype)
        c["v"] = jnp.zeros(shape, dtype)
    return c


def _kv_quant(x):
    """Per (token, head) symmetric int8. x: (..., hd)."""
    am = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s = paper_scale(am, 8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s),
                 qmin(8), qmax(8)).astype(jnp.int8)
    return q, s


def _cache_read(c):
    if c["k"].dtype == jnp.int8:
        k = c["k"].astype(jnp.float32) * c["k_s"]
        v = c["v"].astype(jnp.float32) * c["v_s"]
        return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    return c["k"], c["v"]


def cache_write_prefill(c: dict, k, v) -> dict:
    """Write a full prefill (B, S, G, hd); keeps the last `size` positions
    for rolling caches. S <= max_len by construction."""
    size = c["k"].shape[1]
    s = k.shape[1]
    c = dict(c)
    if s >= size:
        k_keep, v_keep = k[:, s - size:], v[:, s - size:]
        slots = (jnp.arange(s - size, s) % size)
        if c["k"].dtype == jnp.int8:
            kq, ks = _kv_quant(k_keep)
            vq, vs = _kv_quant(v_keep)
            c["k"] = c["k"].at[:, slots].set(kq)
            c["v"] = c["v"].at[:, slots].set(vq)
            c["k_s"] = c["k_s"].at[:, slots].set(ks)
            c["v_s"] = c["v_s"].at[:, slots].set(vs)
        else:
            c["k"] = c["k"].at[:, slots].set(k_keep.astype(c["k"].dtype))
            c["v"] = c["v"].at[:, slots].set(v_keep.astype(c["v"].dtype))
        return c
    if c["k"].dtype == jnp.int8:
        kq, ks = _kv_quant(k)
        vq, vs = _kv_quant(v)
        c["k"] = jax.lax.dynamic_update_slice_in_dim(c["k"], kq, 0, 1)
        c["v"] = jax.lax.dynamic_update_slice_in_dim(c["v"], vq, 0, 1)
        c["k_s"] = jax.lax.dynamic_update_slice_in_dim(c["k_s"], ks, 0, 1)
        c["v_s"] = jax.lax.dynamic_update_slice_in_dim(c["v_s"], vs, 0, 1)
    else:
        c["k"] = jax.lax.dynamic_update_slice_in_dim(
            c["k"], k.astype(c["k"].dtype), 0, 1)
        c["v"] = jax.lax.dynamic_update_slice_in_dim(
            c["v"], v.astype(c["v"].dtype), 0, 1)
    return c


def _cache_write_step(c: dict, k, v, pos) -> dict:
    """Write one token per request. k,v: (B, 1, G, hd); pos: (B,) int32."""
    b = k.shape[0]
    slot = pos % c["k"].shape[1]
    idx = (jnp.arange(b), slot)
    c = dict(c)
    if c["k"].dtype == jnp.int8:
        kq, ks = _kv_quant(k[:, 0])
        vq, vs = _kv_quant(v[:, 0])
        c["k"] = c["k"].at[idx].set(kq)
        c["v"] = c["v"].at[idx].set(vq)
        c["k_s"] = c["k_s"].at[idx].set(ks)
        c["v_s"] = c["v_s"].at[idx].set(vs)
    else:
        c["k"] = c["k"].at[idx].set(k[:, 0].astype(c["k"].dtype))
        c["v"] = c["v"].at[idx].set(v[:, 0].astype(c["v"].dtype))
    return c


def decode_mask(c: dict, pos: jax.Array, window: int) -> jax.Array:
    """(B, 1, 1, size) validity of cache slots for queries at `pos` (B,).

    For slot s and current position P, the stored absolute key position is
    p = P - ((P - s) mod size); valid iff p >= 0, p <= P, and within window.
    """
    size = c["k"].shape[1]
    slots = jnp.arange(size)[None, :]
    pe = pos[:, None]
    kpos = pe - ((pe - slots) % size)
    valid = (kpos >= 0) & (kpos <= pe)
    if window:
        valid &= kpos > pe - window
    return valid[:, None, None, :]


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------

def attn_decode(p, x, cfg, cache: dict, pos: jax.Array, *,
                qcfg: Optional[QuantConfig] = None, impl=None):
    """x: (B, 1, d); pos: (B,) absolute position of this token.
    Returns (out (B,1,d), updated cache)."""
    q, k, v = _qkv(p, x, cfg, pos[:, None], qcfg, impl, None, "")
    cache = _cache_write_step(cache, k, v, pos)
    kc, vc = _cache_read(cache)
    mask = decode_mask(cache, pos, cfg.sliding_window)
    out = _sdpa(q, kc, vc, mask, cfg)
    out = qlinear.apply(p["wo"], out, qcfg, impl)
    return out, cache


def attn_decode_paged(p, x, cfg, pool: dict, page_table: jax.Array,
                      pos: jax.Array, *, qcfg: Optional[QuantConfig] = None,
                      impl=None, paged_impl: Optional[str] = None):
    """Decode step against the paged (optionally int8) KV pool.

    x: (B, 1, d); pos: (B,) absolute write position (== tokens already in
    cache); pool: one block's page pool (serving/kv_pool.py layout);
    page_table: (B, W) physical page ids. paged_impl selects the read path
    as `ops.resolve_impl` resolves it: "xla" (jnp gather oracle) or
    "pallas"/"pallas_interpret" (the scalar-prefetch streaming kernel);
    "auto"/None takes the kernel on TPU. Returns (out (B,1,d), pool)."""
    # Lazy imports: repro.serving imports this module at package init.
    from repro.kernels import ops, paged_attn
    from repro.serving import kv_pool
    paged_impl = ops.resolve_impl(paged_impl)
    q, k, v = _qkv(p, x, cfg, pos[:, None], qcfg, impl, None, "")
    with jax.named_scope("kv_write"):
        pool = kv_pool.write_token(pool, page_table, pos, k[:, 0], v[:, 0])
    kv_len = jnp.maximum(pos + 1, 1)      # dead slots attend scratch page 0
    ks, vs = pool.get("k_s"), pool.get("v_s")
    with jax.named_scope("paged_attention"):
        if paged_impl != "xla":
            out = paged_attn.paged_decode_attention(
                q[:, 0], pool["k"], pool["v"], ks, vs, page_table, kv_len,
                interpret=paged_impl == "pallas_interpret")
        else:
            out = paged_attn.paged_decode_attention_ref(
                q[:, 0], pool["k"], pool["v"], ks, vs, page_table, kv_len)
    out = out.reshape(x.shape[0], 1, -1).astype(x.dtype)
    out = qlinear.apply(p["wo"], out, qcfg, impl)
    return out, pool


class PackedRows(NamedTuple):
    """The mixed step's tokens on one row axis of T rows: each row's
    absolute position `pos` (T,) and lane `lane` (T,), B for a padding row;
    `grid` (B, C) the row that holds each lane's chunk slot, T for an empty
    slot (`transformer.pack_rows` builds them on the host). The paged
    kernels take queries, and `kv_pool.write_chunk` K/V, on the (B, C)
    grid of lanes; everything else runs on the T rows."""
    pos: jax.Array
    lane: jax.Array
    grid: jax.Array

    @property
    def real(self) -> jax.Array:
        """(T,) the rows that hold a token."""
        return self.lane < self.grid.shape[0]

    def to_grid(self, x: jax.Array) -> jax.Array:
        """(T, ...) -> (B, C, ...); an empty slot reads zeros."""
        return jnp.take(x, self.grid, axis=0, mode="fill", fill_value=0)

    def from_grid(self, y: jax.Array, q_start: jax.Array) -> jax.Array:
        """(B, C, ...) -> (T, ...): each row's slot (chunk column pos -
        q_start of its lane); a padding row reads zeros."""
        b, c = self.grid.shape
        lane = jnp.where(self.real, self.lane, 0)
        slot = jnp.where(self.real, lane * c + self.pos - q_start[lane],
                         b * c)
        return jnp.take(y.reshape((b * c,) + y.shape[2:]), slot, axis=0,
                        mode="fill", fill_value=0)


def attn_prefill_chunk_paged(p, x, cfg, pool: dict, page_table: jax.Array,
                             window_rows: jax.Array, rows: PackedRows,
                             q_start: jax.Array, n_new: jax.Array, *,
                             qcfg: Optional[QuantConfig] = None,
                             impl=None, paged_impl: Optional[str] = None):
    """Mixed chunked-prefill/decode attention step against the paged pool.

    x: (T, d) the step's packed rows (`PackedRows`); n_new: (B,) real
    tokens of each lane this step (a prefill chunk, 1 for a decode lane
    riding the mixed step, 0 for an idle lane), from absolute position
    q_start[b]; window_rows: (B, Wc) physical pages covering the chunk's
    write window (kv_pool.write_chunk).

    The projections run on the T rows. Their K/V is gathered onto the
    lanes' (B, C) grid, quantized and written *directly* into its pages
    (fused quantize-on-write — no dense cache), then the grid's queries
    attend causally over everything written so far, so intra-chunk
    attention sees the same (re-rounded) pages decode will; the output
    comes back onto the T rows (padding rows zero) before `wo`. Returns
    (out (T, d), pool). paged_impl resolves as in `attn_decode_paged`."""
    from repro.kernels import ops, paged_prefill
    from repro.serving import kv_pool
    paged_impl = ops.resolve_impl(paged_impl)
    q, k, v = _qkv(p, x[None], cfg, rows.pos[None], qcfg, impl, None, "")
    with jax.named_scope("kv_write"):
        pool = kv_pool.write_chunk(pool, rows.to_grid(k[0]),
                                   rows.to_grid(v[0]), window_rows, q_start,
                                   n_new)
    kv_len = jnp.maximum(q_start + n_new, 1)  # idle slots attend scratch
    ks, vs = pool.get("k_s"), pool.get("v_s")
    q = rows.to_grid(q[0])
    with jax.named_scope("paged_attention"):
        if paged_impl != "xla":
            out = paged_prefill.paged_prefill_attention(
                q, pool["k"], pool["v"], ks, vs, page_table, q_start, kv_len,
                interpret=paged_impl == "pallas_interpret")
        else:
            out = paged_prefill.paged_prefill_attention_ref(
                q, pool["k"], pool["v"], ks, vs, page_table, q_start, kv_len)
    out = rows.from_grid(out, q_start).reshape(x.shape[0], -1)
    out = qlinear.apply(p["wo"], out.astype(x.dtype), qcfg, impl)
    return out, pool


def attn_verify_paged(p, x, cfg, pool: dict, page_table: jax.Array,
                      q_start: jax.Array, n_new: jax.Array, *,
                      qcfg: Optional[QuantConfig] = None, impl=None):
    """Speculative-verify attention step: score a k+1-token draft window
    in one pass against the paged pool, *without writing it*.

    Unlike `attn_prefill_chunk_paged` the window K/V never goes through
    the quantize-on-write path here — the raw projections are spliced
    over the gathered past keys inside the read
    (`ref.paged_verify_attention_ref`), so a fully rejected draft leaves
    the pool untouched and the engine commits only the accepted prefix
    afterwards (`kv_pool.write_chunk`, or `kv_pool.truncate` when a window
    was optimistically written). C = k+1 is not page-aligned.

    There is no Pallas verify kernel: the streaming chunk kernel reads
    pages only, and feeding it the in-flight window needs an extra VMEM
    operand (ROADMAP). So verify runs this jnp gather path on every
    backend. Returns (out (B, C, d), (k, v)) — the raw window projections
    the commit needs."""
    from repro.kernels import ref
    b, c = x.shape[0], x.shape[1]
    positions = q_start[:, None] + jnp.arange(c)[None, :]
    q, k, v = _qkv(p, x, cfg, positions, qcfg, impl, None, "")
    ks, vs = pool.get("k_s"), pool.get("v_s")
    with jax.named_scope("paged_attention"):
        out = ref.paged_verify_attention_ref(
            q, pool["k"], pool["v"], ks, vs, page_table, q_start, n_new,
            k, v)
    out = out.reshape(b, c, -1).astype(x.dtype)
    out = qlinear.apply(p["wo"], out, qcfg, impl)
    return out, (k, v)


def cross_decode(p, x, cfg, cache: dict, *, qcfg=None, impl=None):
    """Cross-attn at decode: context K/V precomputed at prefill."""
    nq, hd = cfg.n_heads, cfg.hd
    q = qlinear.apply(p["wq"], x, qcfg, impl)
    q = _split_heads(q, nq, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["qnorm"]["g"], cfg.norm_eps)
    kc, vc = _cache_read(cache)
    mask = jnp.ones((1, 1, 1, kc.shape[1]), bool)
    out = _sdpa(q, kc, vc, mask, cfg)
    return qlinear.apply(p["wo"], out, qcfg, impl)


def init_cross_cache(cfg, batch: int, kv_bits: int = 16) -> dict:
    nkv, hd = cfg.n_kv_heads, cfg.hd
    t = cfg.n_ctx_tokens
    c = {}
    if kv_bits == 8:
        c["k"] = jnp.zeros((batch, t, nkv, hd), jnp.int8)
        c["v"] = jnp.zeros((batch, t, nkv, hd), jnp.int8)
        c["k_s"] = jnp.zeros((batch, t, nkv, 1), jnp.float32)
        c["v_s"] = jnp.zeros((batch, t, nkv, 1), jnp.float32)
    else:
        c["k"] = jnp.zeros((batch, t, nkv, hd), jnp.bfloat16)
        c["v"] = jnp.zeros((batch, t, nkv, hd), jnp.bfloat16)
    return c


# ---------------------------------------------------------------------------
# Multi-head latent attention (MLA)
# ---------------------------------------------------------------------------
#
# With x the normed hidden state (d), H heads and `cfg.mla` = a:
#
#   c_q = RMSNorm(x W_qa)                          (q_lora_rank)
#   [q_nope_h (nope) | q_rope_h (rope)] = (c_q W_qb)_h
#   [c_kv (kv_lora_rank) | k_rope (rope)] = x W_kva;  c_kv <- RMSNorm(c_kv)
#   q_rope_h, k_rope <- RoPE(theta) at the token's position (k_rope is one
#                       part shared by every head)
#   k_h = [c_kv W_UK,h | k_rope],  v_h = c_kv W_UV,h   (W_kvb = [W_UK|W_UV])
#   score_h(t, s) = (q_nope_h(t) . c_kv(s) W_UK,h + q_rope_h(t) . k_rope(s))
#                   / sqrt(nope + rope)
#   out = concat_h(softmax_s(score_h) . v_h) W_o
#
# The full-sequence form (`mla_forward`) expands k_h and v_h. The paged
# form (`attn_mla_paged`) is absorbed: it stores the latent row
# [c_kv | k_rope] (kv_lora_rank + rope wide, zero-padded to the pool's
# 128-lane rows) once per token and layer,
# scores with q_lat_h = q_nope_h W_UK,h^T against c_kv directly, and maps
# the kv_lora_rank-wide context back through W_UV,h. W_qa, W_qb, W_kva and
# W_o are the quantized linears; W_kvb stays in bfloat16 (its two halves
# multiply per head, not as one linear of the activations).

def init_mla(key, cfg) -> dict:
    a, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 5)
    return {
        "wq_a": qlinear.init_linear(ks[0], d, a.q_lora_rank),
        "q_norm": {"g": jnp.ones((a.q_lora_rank,), jnp.float32)},
        "wq_b": qlinear.init_linear(ks[1], a.q_lora_rank,
                                    h * (a.nope_dim + a.rope_dim)),
        "wkv_a": qlinear.init_linear(ks[2], d, a.latent_dim),
        "kv_norm": {"g": jnp.ones((a.kv_lora_rank,), jnp.float32)},
        "wkv_b": qlinear.init_linear(ks[3], a.kv_lora_rank,
                                     h * (a.nope_dim + a.v_dim)),
        "wo": qlinear.init_linear(ks[4], h * a.v_dim, d),
    }


def _mla_project(p, x, cfg, positions, qcfg, impl, taps, tap_prefix):
    """x (B, S, d) -> q_nope (B,S,H,nope), q_rope (B,S,H,rope) after RoPE,
    latent rows [c_kv | k_rope] (B, S, kv_lora_rank + rope)."""
    a, h = cfg.mla, cfg.n_heads
    if taps is not None:
        taps.record(tap_prefix + "attn_in", x)
    c_q = rms_norm(qlinear.apply(p["wq_a"], x, qcfg, impl),
                   p["q_norm"]["g"], cfg.norm_eps)
    if taps is not None:
        taps.record(tap_prefix + "attn_q_in", c_q)
    q = _split_heads(qlinear.apply(p["wq_b"], c_q, qcfg, impl), h,
                     a.nope_dim + a.rope_dim)
    q_nope, q_rope = q[..., :a.nope_dim], q[..., a.nope_dim:]
    kv = qlinear.apply(p["wkv_a"], x, qcfg, impl)
    c_kv = rms_norm(kv[..., :a.kv_lora_rank], p["kv_norm"]["g"],
                    cfg.norm_eps)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope = apply_rope(kv[..., None, a.kv_lora_rank:], positions,
                        cfg.rope_theta)[..., 0, :]
    return q_nope, q_rope, jnp.concatenate([c_kv, k_rope], axis=-1)


def _mla_up(p, cfg, dtype):
    """W_UK (kv_lora_rank, H, nope) and W_UV (kv_lora_rank, H, v)."""
    a = cfg.mla
    w = p["wkv_b"]["w"].astype(dtype).reshape(
        a.kv_lora_rank, cfg.n_heads, a.nope_dim + a.v_dim)
    return w[..., :a.nope_dim], w[..., a.nope_dim:]


def mla_forward(p, x, cfg, positions, *, lengths=None, qcfg=None, impl=None,
                taps: Optional[Taps] = None, tap_prefix=""):
    """Full-sequence MLA with k_h and v_h expanded from the latent.
    Returns (out (B, S, d), latent rows (B, S, L))."""
    a, h = cfg.mla, cfg.n_heads
    q_nope, q_rope, lat = _mla_project(p, x, cfg, positions, qcfg, impl,
                                       taps, tap_prefix)
    w_uk, w_uv = _mla_up(p, cfg, x.dtype)
    c_kv, k_rope = lat[..., :a.kv_lora_rank], lat[..., a.kv_lora_rank:]
    k_nope = jnp.einsum("bsc,chn->bshn", c_kv, w_uk)
    v = jnp.einsum("bsc,chv->bshv", c_kv, w_uv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :], k_nope.shape[:3] + (a.rope_dim,))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = sdpa_causal(q, k, v, cfg, lengths=lengths)      # (B, S, H*v)
    if taps is not None:
        taps.record(tap_prefix + "attn_out", out)
    return qlinear.apply(p["wo"], out, qcfg, impl), lat


def _mla_absorbed(p, x, cfg, positions, qcfg, impl):
    """The absorbed step's projections of x (B, S, d): q_lat (B, S, H,
    kv_lora_rank), q_rope (B, S, H, rope), the latent rows (B, S,
    kv_lora_rank + rope) and W_UV."""
    q_nope, q_rope, lat = _mla_project(p, x, cfg, positions, qcfg, impl,
                                       None, "")
    w_uk, w_uv = _mla_up(p, cfg, x.dtype)
    with jax.named_scope("mla_absorb"):
        q_lat = jnp.einsum("bshn,chn->bshc", q_nope, w_uk,
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)
    return q_lat, q_rope, lat, w_uv


def _mla_read(cfg, q_lat, q_rope, pool, page_table, q_start, n_new,
              paged_impl):
    """The absorbed queries' (B, C, H, .) context over the latent pages,
    (B, C, H, kv_lora_rank); rows past n_new are undefined."""
    from repro.kernels import ops, paged_mla
    a = cfg.mla
    kv_len = jnp.maximum(q_start + n_new, 1)   # idle lanes attend scratch
    sm = 1.0 / float(a.nope_dim + a.rope_dim) ** 0.5
    impl_p = ops._dispatch(paged_impl, pool["k"].dtype != jnp.uint8,
                           "paged_mla_attention")
    with jax.named_scope("paged_attention"):
        if impl_p != "xla":
            return paged_mla.paged_mla_attention(
                q_lat, q_rope, pool["k"], pool.get("k_s"), page_table,
                q_start, n_new, kv_len, sm=sm,
                interpret=impl_p == "pallas_interpret")
        return paged_mla.paged_mla_attention_ref(
            q_lat, q_rope, pool["k"], pool.get("k_s"), page_table,
            q_start, kv_len, sm=sm)


def _mla_out(p, ctx, w_uv, dtype, qcfg, impl):
    """Context (..., H, kv_lora_rank) through W_UV and W_o -> (..., d)."""
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("...hc,chv->...hv", ctx, w_uv,
                       preferred_element_type=jnp.float32).astype(dtype)
    return qlinear.apply(p["wo"], o.reshape(o.shape[:-2] + (-1,)), qcfg,
                         impl)


def _latent_rows(lat, cfg):
    """Latent rows zero-padded to the pool's row width."""
    pad = cfg.mla.pool_width - lat.shape[-1]
    return jnp.pad(lat, [(0, 0)] * (lat.ndim - 1) + [(0, pad)])


def attn_mla_paged(p, x, cfg, pool: dict, page_table: jax.Array,
                   pos: jax.Array, *, qcfg: Optional[QuantConfig] = None,
                   impl=None, paged_impl: Optional[str] = None):
    """Absorbed MLA decode step against the latent pool (a K-only pool,
    one latent row per token). x (B, 1, d) at positions pos (B,); the row
    is written with `kv_pool.write_token`. paged_impl resolves as in
    `attn_decode_paged`; packed int4 pools run the jnp oracle under
    "auto". Returns (out (B, 1, d), pool)."""
    from repro.serving import kv_pool
    q_lat, q_rope, lat, w_uv = _mla_absorbed(p, x, cfg, pos[:, None], qcfg,
                                             impl)
    with jax.named_scope("kv_write"):
        pool = kv_pool.write_token(pool, page_table, pos,
                                   _latent_rows(lat, cfg), None)
    ctx = _mla_read(cfg, q_lat, q_rope, pool, page_table, pos,
                    jnp.ones_like(pos), paged_impl)
    return _mla_out(p, ctx, w_uv, x.dtype, qcfg, impl), pool


def attn_mla_chunk_paged(p, x, cfg, pool: dict, page_table: jax.Array,
                         window_rows: jax.Array, rows: PackedRows,
                         q_start: jax.Array, n_new: jax.Array, *,
                         qcfg: Optional[QuantConfig] = None, impl=None,
                         paged_impl: Optional[str] = None):
    """Absorbed MLA mixed step: x (T, d) the step's packed rows, lanes as
    in `attn_prefill_chunk_paged`. The projections and the absorption run
    on the T rows; the latent rows go onto the lanes' (B, C) grid and into
    window_rows through `kv_pool.write_chunk`, the absorbed queries onto
    the grid for the kernel, and its context back onto the T rows (padding
    rows zero) before W_UV and W_o. Returns (out (T, d), pool)."""
    from repro.serving import kv_pool
    q_lat, q_rope, lat, w_uv = _mla_absorbed(p, x[None], cfg,
                                             rows.pos[None], qcfg, impl)
    with jax.named_scope("kv_write"):
        lat = rows.to_grid(_latent_rows(lat[0], cfg))
        pool = kv_pool.write_chunk(pool, lat[:, :, None], None, window_rows,
                                   q_start, n_new)
    ctx = _mla_read(cfg, rows.to_grid(q_lat[0]), rows.to_grid(q_rope[0]),
                    pool, page_table, q_start, n_new, paged_impl)
    ctx = rows.from_grid(ctx, q_start)
    return _mla_out(p, ctx, w_uv, x.dtype, qcfg, impl), pool
