"""Pallas TPU kernel: W8A8 int8 GEMM with fused dequantization epilogue.

The paper's framework contribution (§3.1) is native low-bit GEMM on the
Atlas A2 cube unit with dequant folded into the epilogue so no intermediate
format conversions occur. TPU adaptation: int8×int8→int32 on the MXU
(`preferred_element_type=int32`), int32 accumulator held in a VMEM scratch
tile across the K grid dimension, per-token (M) and per-channel (N) float32
scales applied on the accumulator in the final K step before writeback.

Tiling: grid (M/bm, N/bn, K/bk). Blocks are MXU-aligned (multiples of 128 on
the minor dims; int8 native tile is (32, 128) so bk,bn multiples of 128 and
bm multiples of 32 keep layouts packed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu_compiler_params


DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        out = acc * xs_ref[...] * ws_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype",
                                             "interpret"))
def int8_matmul(x_q: jax.Array, w_q: jax.Array,
                x_scale: jax.Array, w_scale: jax.Array,
                *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                bk: int = DEFAULT_BK, out_dtype=jnp.float32,
                interpret: bool = False) -> jax.Array:
    """x_q (M,K) int8, w_q (K,N) int8, x_scale (M,1) f32, w_scale (1,N) f32.

    Requires M % bm == K % bk == N % bn == 0 (ops.py pads + dispatches).
    """
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2, (x_q.shape, w_q.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="int8_matmul",
    )(x_q, w_q, x_scale, w_scale)
