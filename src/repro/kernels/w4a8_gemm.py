"""Pallas TPU kernel: W4A8 GEMM — packed int4 weights unpacked in VMEM.

TPU v5e has no int4 MXU path, so (DESIGN.md §2) the 4-bit win is taken as a
*bandwidth/storage* win: weights live in HBM as two signed nibbles per int8
byte in the grouped-halves layout (`qtypes.pack_int4_halves`) and each
(bk, bn) weight tile is expanded to int8 inside VMEM right before the MXU
dot — one arithmetic-shift pair + a concatenation, no row interleave.

Per-group dequantization: the K grid dimension steps one quantization group
(bk == group_size) at a time; each group's int32 partial product is scaled
by its (1, bn) float32 group scale and accumulated into a float32 VMEM
accumulator, so cross-group accumulation is exact in fp32 (the contract
`ref.w4a8_matmul_ref` checks). The group-scale block is the whole
(K // group_size, bn) column strip — a (1, bn) block would break the TPU
(8, 128) rule on the second-minor dim — and each K step reads its own row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant.qtypes import int4_nibbles
from repro.kernels import tpu_compiler_params


def _kernel(x_ref, wp_ref, xs_ref, gs_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Unpack the (g/2, bn) packed tile -> (g, bn) int8 (values in [-8, 7]).
    lo, hi = int4_nibbles(wp_ref[...])
    w_tile = jnp.concatenate([lo, hi], axis=0).astype(jnp.int8)  # 'halves'

    part = jnp.dot(x_ref[...], w_tile, preferred_element_type=jnp.int32)
    acc_ref[...] += part.astype(jnp.float32) * gs_ref[pl.ds(k, 1), :]

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...] * xs_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("group_size", "bm", "bn",
                                             "out_dtype", "interpret"))
def w4a8_matmul(x_q: jax.Array, w_packed: jax.Array,
                x_scale: jax.Array, w_group_scale: jax.Array,
                *, group_size: int = 128, bm: int = 256, bn: int = 256,
                out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """x_q (M,K) int8; w_packed (K//2,N) int8 'halves'; x_scale (M,1) f32;
    w_group_scale (K//G, N) f32. K must be a multiple of group_size."""
    m, k = x_q.shape
    kp, n = w_packed.shape
    assert kp * 2 == k, (x_q.shape, w_packed.shape)
    g = group_size
    assert k % g == 0 and w_group_scale.shape == (k // g, n)
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)

    grid = (m // bm, n // bn, k // g)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, g), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((g // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((k // g, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="w4a8_matmul",
    )(x_q, w_packed, x_scale, w_group_scale)
