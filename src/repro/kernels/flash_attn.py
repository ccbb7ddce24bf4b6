"""Pallas TPU kernel: causal flash attention (online-softmax, GQA-aware).

The §Perf attribution for glm4-9b x prefill_32k found 4.5 TB/device of f32
score-chain HBM round-trips in the XLA-lowered attention — the score matrix
itself. This kernel is the deployment answer: scores, the running softmax
statistics (m, l) and the output accumulator live in VMEM scratch across
the KV-block loop; HBM traffic reduces to the Q/K/V streams
(FlashAttention adapted to the TPU memory hierarchy: HBM -> VMEM tiles,
MXU for both dots).

Layout: (B*H, S, D) per head-row; GQA maps query head-rows onto shared KV
head-rows inside the BlockSpec index_map (no KV repeat materialization —
the fix the repeated-KV XLA path couldn't express). Grid is
(heads, q_blocks, kv_blocks) with the kv axis innermost ("arbitrary") so
the VMEM accumulators carry across it; fully-masked causal blocks are
skipped with pl.when.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu_compiler_params

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, scale: float, causal: bool, window: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * bq
    k_start = j * bk
    # causal: skip blocks entirely in the future; window: entirely expired
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _block():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal or window:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = kpos <= qpos if causal else jnp.full((bq, bk), True)
            if window:
                mask = jnp.logical_and(mask, kpos > qpos - window)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "hper", "interpret"))
def flash_attention_rows(q, k, v, *, hper: int = 1, causal: bool = True,
                         window: int = 0, bq: int = 512, bk: int = 512,
                         interpret: bool = False):
    """q: (Hq_rows, S, D); k,v: (Hkv_rows, T, D) with Hq_rows = Hkv_rows *
    hper (head-major packing of (B, H): row b*H + h). Returns (Hq_rows, S, D).
    """
    hq, s, d = q.shape
    hkv, t, _ = k.shape
    assert hq == hkv * hper, (q.shape, k.shape, hper)
    bq = min(bq, s)
    bk = min(bk, t)
    while s % bq:
        bq //= 2
    while t % bk:
        bk //= 2
    scale = 1.0 / (d ** 0.5)
    grid = (hq, s // bq, t // bk)
    kern = functools.partial(_kernel, bq=bq, bk=bk, scale=scale,
                             causal=causal, window=window)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda h, i, j, hper=hper: (h // hper, j, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda h, i, j, hper=hper: (h // hper, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hq, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 512, bk: int = 512, interpret: bool = False):
    """Convenience wrapper over (B, S, H, D) / (B, T, G, D) GQA layouts."""
    b, s, h, d = q.shape
    _, t, g, _ = k.shape
    hper = h // g
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * g, t, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * g, t, d)
    out = flash_attention_rows(qr, kr, vr, hper=hper, causal=causal,
                               window=window, bq=bq, bk=bk,
                               interpret=interpret)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
