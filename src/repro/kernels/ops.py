"""Public jit'd wrappers over the Pallas kernels with XLA fallbacks.

Implementation dispatch (`impl`, resolved by `resolve_impl`):
  * "pallas"           — compiled TPU kernels (the deployment path)
  * "pallas_interpret" — same kernel bodies executed in interpret mode
                         (CPU correctness validation; used by tests)
  * "xla"              — plain-jnp int8 HLO path. Numerically identical
                         contract (see ref.py); used on CPU and for the
                         multi-pod dry-run, where XLA's int8 dot carries the
                         cost_analysis FLOPs/bytes for the roofline.
  * "auto" / None      — "pallas" on TPU backends, else "xla".

Wrappers flatten leading batch dims to M and pad M to tile multiples. A
kernel takes K, N in multiples of 128 (W4A8: of the group size): under
"auto" a shape the kernel cannot take runs the XLA path, while an explicit
"pallas" / "pallas_interpret" on such a shape raises instead of quietly
running something other than what was asked for.
"""
from __future__ import annotations

import re
from typing import Optional, Set

import jax
import jax.numpy as jnp

from repro.kernels import int8_gemm, w4a8_gemm, quantize_act, hadamard, ref

IMPLS = ("auto", "pallas", "pallas_interpret", "xla")


def resolve_impl(impl: Optional[str]) -> str:
    """"auto" (or None) -> "pallas" on a TPU backend, "xla" elsewhere; any
    other impl is returned as given."""
    impl = impl or "auto"
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; expected one of {IMPLS}")
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def _dispatch(impl: Optional[str], fits: bool, what: str) -> str:
    """Resolve impl for one call whose shape the kernel can (fits) or
    cannot take: "auto" falls back to "xla" on an unfit shape, an explicit
    kernel impl raises."""
    resolved = resolve_impl(impl)
    if resolved == "xla" or fits:
        return resolved
    if (impl or "auto") == "auto":
        return "xla"
    raise ValueError(f"{what}: impl={resolved!r} requested, but the kernel "
                     "cannot take this shape (see kernels/ops.py)")


_TPU_CUSTOM_CALL = re.compile(
    r'%([\w.-]+) = [^\n]*custom_call_target="tpu_custom_call"')


def compiled_kernels(hlo_text: str) -> Set[str]:
    """Names of the Pallas TPU kernels (their pallas_call `name=`) that a
    compiled program's HLO text (`compiled.as_text()`) calls."""
    return {m.split(".")[0] for m in _TPU_CUSTOM_CALL.findall(hlo_text)}


def _aligned(*dims_mults) -> bool:
    return all(d % m == 0 for d, m in dims_mults)


def _flatten_m(x: jax.Array):
    """(..., K) -> ((M, K), unflatten)"""
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, x.shape[-1])
    return x2, lead


def _pad_m(x: jax.Array, mult: int):
    m = x.shape[0]
    pad = (-m) % mult
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, m


# ---------------------------------------------------------------------------
# INT8 GEMM
# ---------------------------------------------------------------------------

def int8_matmul(x_q, w_q, x_scale, w_scale, *, out_dtype=jnp.float32,
                impl: Optional[str] = None):
    """x_q (..., K) int8 @ w_q (K, N) int8 with fused dequant -> (..., N)."""
    x2, lead = _flatten_m(x_q)
    s2 = x_scale.reshape(x2.shape[0], 1)
    k, n = w_q.shape
    ws = w_scale.reshape(1, n)
    impl = _dispatch(impl, _aligned((k, 128), (n, 128)),
                     f"int8_matmul K={k} N={n}")
    if impl == "xla":
        out = ref.int8_matmul_ref(x2, w_q, s2, ws, out_dtype)
    else:
        interp = impl == "pallas_interpret"
        xp, m0 = _pad_m(x2, 32)
        sp, _ = _pad_m(s2, 32)
        bm = min(int8_gemm.DEFAULT_BM, max(32, xp.shape[0]))
        while xp.shape[0] % bm:
            bm //= 2
        out = int8_gemm.int8_matmul(xp, w_q, sp, ws, bm=bm,
                                    out_dtype=out_dtype, interpret=interp)
        out = out[:m0]
    return out.reshape(lead + (n,))


# ---------------------------------------------------------------------------
# W4A8 GEMM
# ---------------------------------------------------------------------------

def w4a8_matmul(x_q, w_packed, x_scale, w_group_scale, *, group_size: int,
                out_dtype=jnp.float32, impl: Optional[str] = None):
    x2, lead = _flatten_m(x_q)
    s2 = x_scale.reshape(x2.shape[0], 1)
    kp, n = w_packed.shape
    k = kp * 2
    impl = _dispatch(impl, _aligned((k, group_size), (n, 128))
                     and group_size % 2 == 0,
                     f"w4a8_matmul K={k} N={n} group={group_size}")
    if impl == "xla":
        out = ref.w4a8_matmul_ref(x2, w_packed, s2, w_group_scale,
                                  group_size, out_dtype)
    else:
        interp = impl == "pallas_interpret"
        xp, m0 = _pad_m(x2, 32)
        sp, _ = _pad_m(s2, 32)
        bm = min(256, max(32, xp.shape[0]))
        while xp.shape[0] % bm:
            bm //= 2
        out = w4a8_gemm.w4a8_matmul(xp, w_packed, sp, w_group_scale,
                                    group_size=group_size, bm=bm,
                                    out_dtype=out_dtype, interpret=interp)
        out = out[:m0]
    return out.reshape(lead + (n,))


# ---------------------------------------------------------------------------
# Dynamic activation quantization (+ optional fused smooth / FWHT / RMSNorm)
# ---------------------------------------------------------------------------

def quantize_act_dynamic(x, smooth=None, gamma=None, *,
                         hadamard_block: int = 0, rmsnorm_eps: float = 0.0,
                         impl: Optional[str] = None):
    """x (..., K) -> (q int8 (..., K), scale f32 (..., 1))."""
    x2, lead = _flatten_m(x)
    k = x2.shape[1]
    impl = _dispatch(impl, _aligned((k, 128)) and (
        hadamard_block == 0 or k % hadamard_block == 0),
        f"quantize_act_dynamic K={k} hadamard_block={hadamard_block}")
    if impl == "xla":
        if rmsnorm_eps > 0.0 and gamma is not None:
            q, s = ref.fused_rmsnorm_quant_ref(x2, gamma, rmsnorm_eps, smooth)
            if hadamard_block:
                raise NotImplementedError("norm+hadamard fusion unused")
        else:
            q, s = ref.quantize_act_ref(x2, smooth, hadamard_block)
    else:
        interp = impl == "pallas_interpret"
        xp, m0 = _pad_m(x2, 8)
        q, s = quantize_act.quantize_act_dynamic(
            xp, smooth, gamma, hadamard_block=hadamard_block,
            rmsnorm_eps=rmsnorm_eps, interpret=interp)
        q, s = q[:m0], s[:m0]
    return q.reshape(lead + (k,)), s.reshape(lead + (1,))


# ---------------------------------------------------------------------------
# Block Hadamard
# ---------------------------------------------------------------------------

def block_hadamard(x, *, block: int = 128, impl: Optional[str] = None):
    x2, lead = _flatten_m(x)
    k = x2.shape[1]
    impl = _dispatch(impl, k % block == 0, f"block_hadamard K={k}")
    if impl == "xla":
        out = ref.hadamard_ref(x2, block)
    else:
        interp = impl == "pallas_interpret"
        xp, m0 = _pad_m(x2, 8)
        out = hadamard.block_hadamard(xp, block=block, interpret=interp)[:m0]
    return out.reshape(lead + (k,))
