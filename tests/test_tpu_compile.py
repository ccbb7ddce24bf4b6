"""Compile the main-path Pallas kernels for a described TPU v5e chip: the
GEMM kernels at pangu_1b widths (d_model 1536, d_ff 5632) and the chip
smoke's mixed-step rows (8 lanes of 32-token chunks); the paged-attention
kernels at the chip benchmark's serving geometry (openPangu-Embedded-1B's
12 query and 6 KV heads of 128, 16 lanes, a 289-column table of 16-token
pages, 128-token chunks), so the VMEM their page walk takes and the shapes
of its DMAs meet the chip's compiler at the size the benchmark runs.

Nothing runs: the TPU compiler, which is installed here, compiles for a
chip that is described and not attached, and refuses what the chip's
compiler would refuse (block shapes off the (8, 128) tiling, 8-bit shifts,
...) — failures interpret mode cannot show. The topology is described
inside a module fixture, never at import, so every pytest-xdist worker
collects the same tests and only the worker running this file loads the
TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import ops, paged_attn, paged_prefill

CFG = get_arch("pangu_1b")
M = 8 * 32                             # rows of a mixed step's GEMMs
# bench/configs/pangu-1b-w8a8.json as served: heads, lanes, table, chunk
NQ, NKV, HD = 12, 6, 128
LANES, PAGE, COLS, CHUNK = 16, 16, 289, 128
N_PAGES = 1 + LANES * COLS
POOL_DTYPES = {16: jnp.bfloat16, 8: jnp.int8, 4: jnp.uint8}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 with JAX's persistent compilation
    cache off: such a compile is written to the cache but cannot be read
    back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs outside
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        assert topo.devices[0].device_kind == "TPU v5 lite"
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [None if s is None else
            jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kv_bits", [16, 8, 4])
@pytest.mark.parametrize("kernel", ["paged_decode_attention",
                                    "paged_prefill_attention"])
def test_paged_kernel_compiles_for_v5e(one_chip, kernel, kv_bits):
    nq, nkv, hd = NQ, NKV, HD
    stored_hd = hd // 2 if kv_bits == 4 else hd
    pages = ((N_PAGES, nkv, PAGE, stored_hd), POOL_DTYPES[kv_bits])
    scales = None if kv_bits == 16 else ((N_PAGES, nkv), jnp.float32)
    lanes = ((LANES,), jnp.int32)
    table = ((LANES, COLS), jnp.int32)
    if kernel == "paged_decode_attention":
        text = _compile(paged_attn.paged_decode_attention, one_chip,
                        ((LANES, nq, hd), jnp.bfloat16), pages, pages,
                        scales, scales, table, lanes)
    else:
        text = _compile(paged_prefill.paged_prefill_attention, one_chip,
                        ((LANES, CHUNK, nq, hd), jnp.bfloat16), pages, pages,
                        scales, scales, table, lanes, lanes)
    assert ops.compiled_kernels(text) == {kernel}


@pytest.mark.parametrize("kernel", ["int8_matmul", "w4a8_matmul",
                                    "quantize_act_dynamic"])
def test_gemm_kernel_compiles_for_v5e(one_chip, kernel):
    d, ff = CFG.d_model, CFG.d_ff                  # the 1536 -> 5632 GEMM
    xs = ((M, 1), jnp.float32)
    if kernel == "int8_matmul":
        text = _compile(
            lambda x, w, a, b: ops.int8_matmul(x, w, a, b, impl="pallas"),
            one_chip, ((M, d), jnp.int8), ((d, ff), jnp.int8), xs,
            ((ff,), jnp.float32))
    elif kernel == "w4a8_matmul":
        g = 128
        text = _compile(
            lambda x, w, a, b: ops.w4a8_matmul(x, w, a, b, group_size=g,
                                               impl="pallas"),
            one_chip, ((M, d), jnp.int8), ((d // 2, ff), jnp.int8), xs,
            ((d // g, ff), jnp.float32))
    else:
        text = _compile(lambda x: ops.quantize_act_dynamic(x, impl="pallas"),
                        one_chip, ((M, d), jnp.bfloat16))
    assert ops.compiled_kernels(text) == {kernel}
