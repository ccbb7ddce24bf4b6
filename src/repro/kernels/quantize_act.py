"""Pallas TPU kernel: dynamic per-token activation quantization.

One VMEM pass produces (int8 values, per-token f32 scales) — the runtime
half of W8A8/W4A8. Optional fusions (compile-time flags), mirroring the
paper's "no intermediate format conversion" principle by keeping the whole
pre-GEMM pipeline in one kernel:

  * SmoothQuant:  X <- X / s        (per-channel diagonal, Eq. 3)
  * Hadamard:     X <- X H_block    (block-FWHT butterfly in VMEM, Eq. 4)
  * RMSNorm:      X <- rmsnorm(X)*gamma  (beyond-paper fused epilogue —
                  QServe-style; removes a full HBM round-trip per layer)

Row-blocked: grid over M, full K resident per block (per-token absmax needs
the whole feature dim; block height auto-sized to the VMEM budget).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant.qtypes import qmax, qmin, scale_denom
from repro.kernels import tpu_compiler_params

_QMAX = float(qmax(8))
_QMIN = float(qmin(8))              # canonical narrow symmetric range
_SCALE_DENOM = scale_denom(8)       # paper Eq. 2: s = 2*max|X| / (2^8 - 1)
_VMEM_BUDGET = 6 * 1024 * 1024  # bytes of f32 working set per block


def _fwht(t: jax.Array, block: int) -> jax.Array:
    """In-register block FWHT along the last axis. t: (bm, K) f32."""
    bm, k = t.shape
    t = t.reshape(bm, k // block, block)
    h = 1
    while h < block:
        t = t.reshape(bm, k // block, block // (2 * h), 2, h)
        a = t[..., 0, :]
        b = t[..., 1, :]
        t = jnp.concatenate([a + b, a - b], axis=-1)
        h *= 2
    return t.reshape(bm, k) * (1.0 / jnp.sqrt(jnp.float32(block)))


def _make_kernel(has_smooth: bool, hadamard_block: int, has_norm: bool,
                 eps: float):
    def kernel(*refs):
        idx = 0
        x_ref = refs[idx]; idx += 1
        s_ref = refs[idx] if has_smooth else None
        idx += int(has_smooth)
        g_ref = refs[idx] if has_norm else None
        idx += int(has_norm)
        q_ref, scale_ref = refs[idx], refs[idx + 1]

        t = x_ref[...].astype(jnp.float32)
        if has_norm:
            rms = jnp.sqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)
            t = t / rms * g_ref[...].astype(jnp.float32)
        if has_smooth:
            t = t / s_ref[...].astype(jnp.float32)
        if hadamard_block:
            t = _fwht(t, hadamard_block)
        absmax = jnp.max(jnp.abs(t), axis=-1, keepdims=True)
        scale = jnp.maximum(2.0 * absmax / _SCALE_DENOM, 1e-8)
        q = jnp.clip(jnp.round(t / scale), _QMIN, _QMAX)
        q_ref[...] = q.astype(jnp.int8)
        scale_ref[...] = scale

    return kernel


def _pick_bm(m: int, k: int) -> int:
    bm = max(8, _VMEM_BUDGET // (k * 4))
    bm = 1 << (bm.bit_length() - 1)          # round down to a power of two
    bm = min(bm, 512)
    while m % bm != 0:
        bm //= 2
    return max(bm, 1)


@functools.partial(jax.jit, static_argnames=("hadamard_block", "rmsnorm_eps",
                                             "interpret"))
def quantize_act_dynamic(x: jax.Array, smooth=None, gamma=None, *,
                         hadamard_block: int = 0,
                         rmsnorm_eps: float = 0.0,
                         interpret: bool = False):
    """x (M,K) float -> (q (M,K) int8, scale (M,1) f32).

    smooth: optional (K,) f32 divisor; gamma: optional (K,) RMSNorm gain
    (rmsnorm_eps > 0 enables the fused-norm path).
    """
    m, k = x.shape
    has_smooth = smooth is not None
    has_norm = gamma is not None
    bm = _pick_bm(m, k)

    in_specs = [pl.BlockSpec((bm, k), lambda i: (i, 0))]
    args = [x]
    if has_smooth:
        in_specs.append(pl.BlockSpec((1, k), lambda i: (0, 0)))
        args.append(smooth.reshape(1, k))
    if has_norm:
        assert rmsnorm_eps > 0.0
        in_specs.append(pl.BlockSpec((1, k), lambda i: (0, 0)))
        args.append(gamma.reshape(1, k))

    q, scale = pl.pallas_call(
        _make_kernel(has_smooth, hadamard_block, has_norm, rmsnorm_eps),
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((m, k), jnp.int8),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="quantize_act_dynamic",
    )(*args)
    return q, scale
