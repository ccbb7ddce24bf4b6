"""Pallas TPU kernels for the low-bit inference framework.

Modules: int8_gemm, w4a8_gemm, quantize_act, hadamard, paged_attn,
paged_prefill, flash_attn (kernels); ops (jit'd wrappers + dispatch); ref
(pure-jnp oracles).

Every kernel builds its compiler params through `tpu_compiler_params`, the
one construction point quantlint's `pallas-compiler-params` rule checks.
"""
from jax.experimental.pallas import tpu as _pltpu


def tpu_compiler_params(**kwargs):
    """Construct the Pallas TPU compiler params."""
    return _pltpu.CompilerParams(**kwargs)
