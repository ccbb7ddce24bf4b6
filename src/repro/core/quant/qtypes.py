"""Quantization data types: configs, quantized tensors, int4 packing.

Implements the paper's symmetric scheme (Eq. 1-2):
    s    = 2*max(|X|) / (2^n - 1)
    Xbar = clamp(round(X / s), qmin(n), qmax(n))

**Canonical clip range.** The repo-wide symmetric range is the *narrow*
one: [qmin(n), qmax(n)] = [-(2^(n-1) - 1), 2^(n-1) - 1], i.e. [-127, 127]
for int8 and [-7, 7] for int4 — the paper-faithful W8A8 weight range. The
grid stays sign-symmetric (dequantization commutes with negation) and
int8 x int8 products keep 1 spare bit of int32 headroom. The two's-
complement storage minimum (-128 / -8) is available as `qmin_storage(n)`
but is *not* a valid quantized value; earlier revisions mixed both ranges
across files, which is exactly the silent-divergence class of bug the
`repro.analysis` checker now rejects (magic-quant-literal rule: all call
sites must go through `qmin(bits)` / `qmax(bits)`).

Weights are quantized per-output-channel (8-bit) or per-group along the
reduction dim (4-bit, group_size=128 default); activations per-token,
dynamically at runtime. All scales are float32.

INT4 storage: two signed nibbles packed per int8 byte along the reduction
(K) axis — byte = (hi << 4) | (lo & 0xF); unpacking uses arithmetic shifts
for sign extension. This mirrors the Atlas A2 packed-weight layout the
paper configures in CATLASS, adapted to TPU VMEM tiles (see DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

WEIGHT_GRANULARITIES = ("per_tensor", "per_channel", "per_group")
ACT_GRANULARITIES = ("per_tensor", "per_token")


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Static configuration of a PTQ scheme.

    Presets below cover the paper's four evaluated settings: INT8 (W8A8),
    W4A8 baseline, W4A8+SmoothQuant, W4A8+Hadamard.
    """

    weight_bits: int = 8                # 4 or 8
    act_bits: int = 8                   # 8 or 16 (16 = weight-only)
    weight_granularity: str = "per_channel"
    act_granularity: str = "per_token"
    group_size: int = 128               # for per_group weights (along K)
    smooth: bool = False                # SmoothQuant diagonal scaling
    smooth_alpha: float = 0.5           # paper uses alpha = 0.5
    hadamard: bool = False              # QuaRot-style block rotation
    hadamard_block: int = 128           # block size of the online FWHT
    kv_bits: int = 16                   # 8 => int8, 4 => packed-int4 KV
                                        # cache (beyond-paper)
    symmetric: bool = True              # paper: symmetric only

    def __post_init__(self):
        assert self.weight_bits in (4, 8), self.weight_bits
        assert self.act_bits in (8, 16), self.act_bits
        assert self.weight_granularity in WEIGHT_GRANULARITIES
        assert self.act_granularity in ACT_GRANULARITIES
        assert self.symmetric, "paper evaluates symmetric quantization only"
        if self.weight_bits == 4:
            assert self.weight_granularity in ("per_group", "per_channel")

    @property
    def is_quantized(self) -> bool:
        return self.weight_bits < 16

    @property
    def name(self) -> str:
        tag = f"w{self.weight_bits}a{self.act_bits}"
        if self.smooth:
            tag += "-smooth"
        if self.hadamard:
            tag += "-hadamard"
        return tag


# The paper's four evaluated configurations (Tables 1-2).
FP16 = None  # sentinel: no quantization
INT8 = QuantConfig(weight_bits=8, act_bits=8)
W4A8 = QuantConfig(weight_bits=4, act_bits=8, weight_granularity="per_group")
W4A8_SMOOTH = dataclasses.replace(W4A8, smooth=True)
# smooth_alpha < 0: per-site migration-strength search (smooth.search_alpha)
W4A8_SMOOTH_AUTO = dataclasses.replace(W4A8, smooth=True, smooth_alpha=-1.0)
W4A8_HADAMARD = dataclasses.replace(W4A8, hadamard=True)

PRESETS = {
    "fp16": FP16,
    "bf16": FP16,
    "int8": INT8,
    "w8a8": INT8,
    "w4a8": W4A8,
    "w4a8-smooth": W4A8_SMOOTH,
    "w4a8-smooth-auto": W4A8_SMOOTH_AUTO,
    "w4a8-hadamard": W4A8_HADAMARD,
}


def preset(name: str) -> Optional[QuantConfig]:
    key = name.lower()
    if key not in PRESETS:
        raise KeyError(f"unknown quant preset {name!r}; have {sorted(PRESETS)}")
    return PRESETS[key]


# ---------------------------------------------------------------------------
# Scale computation (paper Eq. 2)
# ---------------------------------------------------------------------------

def qmax(bits: int) -> int:
    """Largest quantized value: 2^(n-1) - 1 (127 for int8, 7 for int4)."""
    return 2 ** (bits - 1) - 1


def qmin(bits: int) -> int:
    """Smallest quantized value — the canonical *narrow symmetric* bound
    -(2^(n-1) - 1), NOT the two's-complement storage minimum (see module
    docstring)."""
    return -qmax(bits)


def qmin_storage(bits: int) -> int:
    """Two's-complement storage minimum (-128 for int8). Valid as a storage
    bit pattern only; quantized values are clipped to [qmin, qmax]."""
    return -(2 ** (bits - 1))


def scale_denom(bits: int) -> float:
    """Denominator of the paper's Eq. 2 scale: 2^n - 1 levels."""
    return float(2 ** bits - 1)


def paper_scale(absmax: jax.Array, bits: int) -> jax.Array:
    """s = 2*max|X| / (2^n - 1). Guards zero rows with eps."""
    s = 2.0 * absmax.astype(jnp.float32) / scale_denom(bits)
    return jnp.maximum(s, 1e-8)


# ---------------------------------------------------------------------------
# QTensor pytree
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class QTensor:
    """A symmetric-quantized tensor.

    data:  int8 storage. If bits == 4, two nibbles are packed per byte along
           axis `pack_axis` (so data.shape[pack_axis] == orig/2).
    scale: float32 broadcastable against the *unpacked* integer data for
           dequantization, except per-group weights where scale has shape
           (K // group_size, N) and dequant is group-blocked.
    """

    data: jax.Array
    scale: jax.Array
    bits: int
    group_size: int = 0           # 0 => not grouped
    pack_axis: int = 0            # axis nibbles were packed along (bits==4)
    orig_dim: int = 0             # unpacked length of pack_axis (bits==4)
    layout: str = "interleave"    # "interleave" | "halves" (kernel layout)

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten_with_keys(self):
        from jax.tree_util import GetAttrKey
        children = ((GetAttrKey("data"), self.data),
                    (GetAttrKey("scale"), self.scale))
        return children, (self.bits, self.group_size, self.pack_axis,
                          self.orig_dim, self.layout)

    def tree_flatten(self):
        return (self.data, self.scale), (self.bits, self.group_size,
                                         self.pack_axis, self.orig_dim,
                                         self.layout)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scale = children
        bits, group_size, pack_axis, orig_dim, layout = aux
        return cls(data, scale, bits, group_size, pack_axis, orig_dim, layout)

    # -- helpers ------------------------------------------------------------
    @property
    def is_packed(self) -> bool:
        return self.bits == 4

    @property
    def shape(self):
        if not self.is_packed:
            return self.data.shape
        s = list(self.data.shape)
        s[self.pack_axis] = self.orig_dim
        return tuple(s)

    def unpacked(self) -> jax.Array:
        """int8 array of logical shape (values in [-8, 7] when bits==4)."""
        if not self.is_packed:
            return self.data
        if self.layout == "halves":
            g = self.group_size or self.orig_dim
            return unpack_int4_halves(self.data, g)
        return unpack_int4(self.data, self.pack_axis, self.orig_dim)

    def dequantize(self, dtype=jnp.float32) -> jax.Array:
        w = self.unpacked().astype(jnp.float32)
        if self.group_size:
            k, n = w.shape
            g = self.group_size
            w = w.reshape(k // g, g, n) * self.scale[:, None, :]
            w = w.reshape(k, n)
        else:
            w = w * self.scale
        return w.astype(dtype)


# ---------------------------------------------------------------------------
# INT4 packing
# ---------------------------------------------------------------------------

def pack_int4(x: jax.Array, axis: int = 0) -> jax.Array:
    """Pack int8 values in [-8, 7] pairwise along `axis` into bytes."""
    assert x.dtype == jnp.int8
    assert x.shape[axis] % 2 == 0, f"axis {axis} of {x.shape} must be even"
    x = jnp.moveaxis(x, axis, 0)
    lo = x[0::2]
    hi = x[1::2]
    packed = ((hi << 4) | (lo & 0x0F)).astype(jnp.int8)
    return jnp.moveaxis(packed, 0, axis)


def int4_nibbles(packed: jax.Array):
    """Sign-extended (low, high) nibbles of packed int4 bytes, as int32.

    The bytes are widened to int32 *before* the shifts: Mosaic (the Pallas
    TPU compiler) has no 8-bit shifts, and a 32-bit lane gives the same
    arithmetic-shift sign extension for int8 and uint8 storage alike —
    `(b << 28) >> 28` keeps the low nibble, `(b << 24) >> 28` the high one.
    Every unpack in the repo (XLA and kernel bodies) goes through here."""
    b = packed.astype(jnp.int32)
    return (b << 28) >> 28, (b << 24) >> 28


def unpack_int4(packed: jax.Array, axis: int = 0, orig_dim: int = 0) -> jax.Array:
    """Inverse of pack_int4 — arithmetic shifts sign-extend the nibbles."""
    assert packed.dtype == jnp.int8
    p = jnp.moveaxis(packed, axis, 0)
    lo, hi = int4_nibbles(p)
    out = jnp.stack([lo, hi], axis=1).reshape((-1,) + p.shape[1:])
    if orig_dim:
        out = out[:orig_dim]
    return jnp.moveaxis(out, 0, axis).astype(jnp.int8)


def pack_int4_halves(x: jax.Array, group: int) -> jax.Array:
    """Deployment ("CATLASS-style") packed layout used by the W4A8 kernel.

    Within each `group` of rows along axis 0, packed byte row i holds
    (lo = row i, hi = row i + group/2), so in-kernel unpacking is a plain
    concatenation of two sign-extended halves — no row interleave, which is
    the TPU-sublane-friendly analogue of the paper's custom weight layout.
    x: (K, N) int8 in [-8, 7], K % group == 0 -> (K//2, N) int8.
    """
    assert x.dtype == jnp.int8 and x.ndim == 2
    k, n = x.shape
    assert group % 2 == 0 and k % group == 0, (k, group)
    xg = x.reshape(k // group, group, n)
    lo = xg[:, : group // 2]
    hi = xg[:, group // 2:]
    packed = ((hi << 4) | (lo & 0x0F)).astype(jnp.int8)
    return packed.reshape(k // 2, n)


def unpack_int4_halves(packed: jax.Array, group: int) -> jax.Array:
    """Inverse of pack_int4_halves. packed: (K//2, N) -> (K, N) int8."""
    assert packed.dtype == jnp.int8 and packed.ndim == 2
    k2, n = packed.shape
    g2 = group // 2
    pg = packed.reshape(k2 // g2, g2, n)
    lo, hi = int4_nibbles(pg)
    out = jnp.concatenate([lo, hi], axis=1)  # (K//g, g, N)
    return out.reshape(2 * k2, n).astype(jnp.int8)


def pack_int4_halves_lastdim(x: jax.Array) -> jax.Array:
    """Grouped-halves pack along the *last* axis — the paged KV-pool page
    layout (group == the whole last dim: byte j holds lo = x[..., j],
    hi = x[..., j + D/2]). Unlike the weight-side `pack_int4_halves` the
    packed dtype is uint8, so pool code and kernels can discriminate
    packed-int4 pages from plain int8 pages by dtype alone.
    x: (..., D) int8 in [-8, 7], D even -> (..., D//2) uint8.
    """
    assert x.dtype == jnp.int8
    d = x.shape[-1]
    assert d % 2 == 0, f"last dim {d} must be even to nibble-pack"
    lo = x[..., : d // 2]
    hi = x[..., d // 2:]
    return ((hi << 4) | (lo & 0x0F)).astype(jnp.uint8)


def unpack_int4_halves_lastdim(packed: jax.Array) -> jax.Array:
    """Inverse of pack_int4_halves_lastdim: (..., D//2) uint8 -> (..., D)
    int8, through the int32-widened shifts of `int4_nibbles`, so the same
    code runs inside Pallas kernel bodies."""
    assert packed.dtype == jnp.uint8
    lo, hi = int4_nibbles(packed)
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.int8)


# ---------------------------------------------------------------------------
# Quantize / dequantize / fake-quant (reference semantics)
# ---------------------------------------------------------------------------

def _reduce_absmax(x: jax.Array, axis, keepdims=True) -> jax.Array:
    return jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=keepdims)


def quantize_weight(w: jax.Array, cfg: QuantConfig) -> QTensor:
    """Quantize a (K, N) weight: per-channel (scale (1,N)) or per-group
    (scale (K//g, N)); 4-bit results are nibble-packed along K."""
    assert w.ndim == 2, f"weights must be (K, N); got {w.shape}"
    k, n = w.shape
    bits = cfg.weight_bits
    if cfg.weight_granularity == "per_group" and bits == 4:
        # Largest group <= cfg.group_size that divides K (e.g. hymba's
        # d=1600 -> 64). Falls back to per-channel when K is too ragged.
        import math
        g = math.gcd(cfg.group_size, k)
        if g < 8 or g % 2:
            cfg = dataclasses.replace(cfg, weight_granularity="per_channel")
            return quantize_weight(w, cfg)
        assert k % g == 0, f"K={k} not divisible by group_size={g}"
        wg = w.reshape(k // g, g, n)
        scale = paper_scale(_reduce_absmax(wg, axis=1, keepdims=False), bits)
        q = jnp.clip(jnp.round(wg / scale[:, None, :]), qmin(bits), qmax(bits))
        q = q.reshape(k, n).astype(jnp.int8)
        return QTensor(pack_int4_halves(q, g), scale, bits, group_size=g,
                       pack_axis=0, orig_dim=k, layout="halves")
    if cfg.weight_granularity == "per_tensor":
        scale = paper_scale(_reduce_absmax(w, axis=None), bits)
    else:  # per_channel over output dim N: reduce K
        scale = paper_scale(_reduce_absmax(w, axis=0, keepdims=True), bits)
    q = jnp.clip(jnp.round(w / scale), qmin(bits), qmax(bits)).astype(jnp.int8)
    if bits == 4:
        return QTensor(pack_int4(q, 0), scale, bits, pack_axis=0, orig_dim=k)
    return QTensor(q, scale, bits)


def quantize_act(x: jax.Array, bits: int = 8,
                 granularity: str = "per_token"):
    """Dynamic activation quantization. x: (..., K). Returns (q, scale) with
    scale shaped (..., 1) for per_token or scalar-like for per_tensor."""
    if granularity == "per_token":
        scale = paper_scale(_reduce_absmax(x, axis=-1), bits)
    else:
        scale = paper_scale(_reduce_absmax(x, axis=None), bits)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                 qmin(bits), qmax(bits)).astype(jnp.int8)
    return q, scale


def fake_quant(x: jax.Array, bits: int, axis=None, group_size: int = 0) -> jax.Array:
    """Quantize-dequantize in float — the simulation oracle used by accuracy
    benchmarks (identical rounding semantics to the integer path)."""
    xf = x.astype(jnp.float32)
    if group_size:
        assert x.ndim == 2 and x.shape[0] % group_size == 0
        k, n = x.shape
        xg = xf.reshape(k // group_size, group_size, n)
        scale = paper_scale(_reduce_absmax(xg, axis=1), bits)
        q = jnp.clip(jnp.round(xg / scale), qmin(bits), qmax(bits))
        return (q * scale).reshape(k, n).astype(x.dtype)
    scale = paper_scale(_reduce_absmax(xf, axis=axis), bits)
    q = jnp.clip(jnp.round(xf / scale), qmin(bits), qmax(bits))
    return (q * scale).astype(x.dtype)
