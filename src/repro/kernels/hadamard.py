"""Pallas TPU kernel: standalone block Walsh-Hadamard transform.

Online activation rotation (paper Eq. 4, QuaRot-style) for sites where the
rotation is *not* fused into the quantization kernel (e.g. rotating values
feeding an unquantized op). Butterfly runs entirely in VMEM registers:
log2(block) add/sub sweeps, O(K log b) instead of a (K, K) matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tpu_compiler_params
from repro.kernels.quantize_act import _fwht, _pick_bm


def _kernel_factory(block: int):
    def kernel(x_ref, o_ref):
        t = x_ref[...].astype(jnp.float32)
        o_ref[...] = _fwht(t, block).astype(o_ref.dtype)
    return kernel


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def block_hadamard(x: jax.Array, *, block: int = 128,
                   interpret: bool = False) -> jax.Array:
    """x (M, K) -> X H_block (same shape/dtype). K % block == 0."""
    m, k = x.shape
    assert k % block == 0, (k, block)
    bm = _pick_bm(m, k)
    return pl.pallas_call(
        _kernel_factory(block),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, k), x.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="block_hadamard",
    )(x)
