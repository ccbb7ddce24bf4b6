"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes, plus the xla fallback wrappers."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.quant import qtypes
from repro.kernels import ref, ops
from repro.kernels import int8_gemm, w4a8_gemm, quantize_act, hadamard


def rng(seed=0):
    return np.random.default_rng(seed)


# ---------------------------------------------------------------------------
# INT8 GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(32, 128, 128), (128, 256, 384),
                                   (64, 512, 128), (256, 128, 256)])
def test_int8_gemm_matches_ref(m, k, n):
    r = rng(m + k + n)
    x = r.integers(-127, 128, (m, k)).astype(np.int8)
    w = r.integers(-127, 128, (k, n)).astype(np.int8)
    xs = r.uniform(0.001, 0.1, (m, 1)).astype(np.float32)
    ws = r.uniform(0.001, 0.1, (1, n)).astype(np.float32)
    got = int8_gemm.int8_matmul(x, w, xs, ws, bm=32, bn=128, bk=128,
                                interpret=True)
    want = ref.int8_matmul_ref(x, w, xs, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_int8_gemm_out_dtypes(out_dtype):
    r = rng(7)
    x = r.integers(-127, 128, (64, 128)).astype(np.int8)
    w = r.integers(-127, 128, (128, 128)).astype(np.int8)
    xs = r.uniform(0.001, 0.1, (64, 1)).astype(np.float32)
    ws = r.uniform(0.001, 0.1, (1, 128)).astype(np.float32)
    got = int8_gemm.int8_matmul(x, w, xs, ws, bm=32, bn=128, bk=128,
                                out_dtype=out_dtype, interpret=True)
    want = ref.int8_matmul_ref(x, w, xs, ws, out_dtype)
    assert got.dtype == out_dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-2)


def test_int8_gemm_wrapper_pads_and_batches():
    r = rng(3)
    x = r.integers(-127, 128, (2, 5, 7, 128)).astype(np.int8)  # odd M=70
    w = r.integers(-127, 128, (128, 256)).astype(np.int8)
    xs = r.uniform(0.001, 0.1, (2, 5, 7, 1)).astype(np.float32)
    ws = r.uniform(0.001, 0.1, (256,)).astype(np.float32)
    got = ops.int8_matmul(x, w, xs, ws, impl="pallas_interpret")
    want = ops.int8_matmul(x, w, xs, ws, impl="xla")
    assert got.shape == (2, 5, 7, 256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


# ---------------------------------------------------------------------------
# W4A8 GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n,g", [(32, 256, 128, 128), (64, 512, 256, 128),
                                     (16, 128, 128, 64), (128, 256, 384, 256)])
def test_w4a8_gemm_matches_ref(m, k, n, g):
    r = rng(m * 7 + k + n + g)
    x = r.integers(-127, 128, (m, k)).astype(np.int8)
    w4 = r.integers(-8, 8, (k, n)).astype(np.int8)
    wp = qtypes.pack_int4_halves(jnp.asarray(w4), g)
    xs = r.uniform(0.001, 0.1, (m, 1)).astype(np.float32)
    gs = r.uniform(0.001, 0.1, (k // g, n)).astype(np.float32)
    got = w4a8_gemm.w4a8_matmul(x, wp, xs, gs, group_size=g, bm=16, bn=128,
                                interpret=True)
    want = ref.w4a8_matmul_ref(x, wp, xs, gs, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_w4a8_pack_unpack_roundtrip_halves():
    r = rng(11)
    w4 = jnp.asarray(r.integers(-8, 8, (512, 64)).astype(np.int8))
    packed = qtypes.pack_int4_halves(w4, 128)
    assert packed.shape == (256, 64)
    back = qtypes.unpack_int4_halves(packed, 128)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(w4))


def test_w4a8_wrapper_fallback_unaligned(monkeypatch):
    r = rng(5)
    x = r.integers(-127, 128, (10, 256)).astype(np.int8)   # M=10 unaligned
    w4 = r.integers(-8, 8, (256, 96)).astype(np.int8)      # N=96 unaligned
    wp = qtypes.pack_int4_halves(jnp.asarray(w4), 128)
    xs = r.uniform(0.001, 0.1, (10, 1)).astype(np.float32)
    gs = r.uniform(0.001, 0.1, (2, 96)).astype(np.float32)
    want = ref.w4a8_matmul_ref(x, wp, xs, gs, 128)
    # an explicit kernel request on a shape the kernel cannot take raises
    with pytest.raises(ValueError, match="cannot take this shape"):
        ops.w4a8_matmul(x, wp, xs, gs, group_size=128,
                        impl="pallas_interpret")
    # "auto" on a TPU backend falls back to the XLA path for that shape
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    got = ops.w4a8_matmul(x, wp, xs, gs, group_size=128, impl="auto")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


# ---------------------------------------------------------------------------
# Dynamic activation quant (+ fusions)
# ---------------------------------------------------------------------------

def _assert_int8_close(got, want, max_frac=0.01):
    """Quantized values may differ by 1 level at the +-127.5 clip boundary
    (paper Eq. 2 denominator 2^n - 1) due to XLA division reassociation."""
    diff = np.abs(np.asarray(got, np.int32) - np.asarray(want, np.int32))
    assert (diff <= 1).all(), f"max diff {diff.max()}"
    assert (diff != 0).mean() <= max_frac, f"{(diff != 0).mean():.4f} differ"


@pytest.mark.parametrize("m,k", [(8, 128), (64, 256), (17, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_act_matches_ref(m, k, dtype):
    r = rng(m + k)
    x = jnp.asarray(r.normal(0, 3, (m, k)), dtype)
    q, s = ops.quantize_act_dynamic(x, impl="pallas_interpret")
    qr, sr = ref.quantize_act_ref(x)
    _assert_int8_close(q, qr)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


def test_quantize_act_fused_smooth():
    r = rng(21)
    x = jnp.asarray(r.normal(0, 1, (32, 256)), jnp.float32)
    sm = jnp.asarray(r.uniform(0.5, 2.0, (256,)), jnp.float32)
    q, s = ops.quantize_act_dynamic(x, sm, impl="pallas_interpret")
    qr, sr = ref.quantize_act_ref(x, sm)
    _assert_int8_close(q, qr)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)


def test_quantize_act_fused_hadamard():
    r = rng(22)
    x = jnp.asarray(r.normal(0, 1, (16, 256)), jnp.float32)
    q, s = ops.quantize_act_dynamic(x, hadamard_block=128,
                                    impl="pallas_interpret")
    qr, sr = ref.quantize_act_ref(x, hadamard_block=128)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
    # rounding at +-0.5 boundaries can flip by 1 ulp of int; allow tiny diff
    diff = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    assert (diff <= 1).all() and (diff != 0).mean() < 0.01


def test_quantize_act_fused_rmsnorm():
    r = rng(23)
    x = jnp.asarray(r.normal(0, 1, (32, 128)), jnp.float32)
    g = jnp.asarray(r.uniform(0.5, 1.5, (128,)), jnp.float32)
    q, s = ops.quantize_act_dynamic(x, gamma=g, rmsnorm_eps=1e-6,
                                    impl="pallas_interpret")
    qr, sr = ref.fused_rmsnorm_quant_ref(x, g, 1e-6)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5)
    diff = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    assert (diff <= 1).all()


# ---------------------------------------------------------------------------
# Hadamard kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,b", [(8, 128, 128), (32, 512, 128), (16, 256, 64)])
def test_hadamard_kernel_matches_ref(m, k, b):
    r = rng(m + k + b)
    x = jnp.asarray(r.normal(0, 1, (m, k)), jnp.float32)
    got = hadamard.block_hadamard(x, block=b, interpret=True)
    want = ref.hadamard_ref(x, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_hadamard_orthogonal_roundtrip():
    r = rng(9)
    x = jnp.asarray(r.normal(0, 1, (8, 256)), jnp.float32)
    y = hadamard.block_hadamard(x, block=128, interpret=True)
    back = hadamard.block_hadamard(y, block=128, interpret=True)
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               rtol=1e-4, atol=1e-5)
