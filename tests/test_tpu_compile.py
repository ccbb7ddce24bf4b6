"""Compile the main-path Pallas kernels for a described TPU v5e chip: the
GEMM kernels at pangu_1b widths (d_model 1536, d_ff 5632) and the chip
smoke's mixed-step rows (8 lanes of 32-token chunks); the paged-attention
kernels at the chip benchmark's serving geometry (openPangu-Embedded-1B's
12 query and 6 KV heads of 128, 16 lanes, a 289-column table of 16-token
pages, 128-token chunks), so the VMEM their page walk takes and the shapes
of its DMAs meet the chip's compiler at the size the benchmark runs; and
openPangu-Ultra-MoE's two kernels at its cell's geometry (latent pages of
576 for 128 heads, 64 lanes, a 641-column table; 8 held experts of 7680 ->
2 x 2048 -> 7680, the rows of a decode and of a mixed step). The engine's
decode program and its mixed program at every size of its packed row axis
compile too, at both cells' page counts, to check that they write the KV
pools in place.

Nothing runs: the TPU compiler, which is installed here, compiles for a
chip that is described and not attached, and refuses what the chip's
compiler would refuse (block shapes off the (8, 128) tiling, 8-bit shifts,
...) — failures interpret mode cannot show. The topology is described
inside a module fixture, never at import, so every pytest-xdist worker
collects the same tests and only the worker running this file loads the
TPU library.
"""
import dataclasses
import math
import re

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


# bench/configs/pangu-ultra-moe-w8a8.json as served
MLA_H, MLA_R, MLA_P = 128, 512, 64
MLA_LANES, MLA_COLS, MLA_PAGES = 64, 641, 41025
HIDDEN, EXPERT_FF, HELD, TOP_K = 7680, 2048, 8, 8


@pytest.mark.parametrize("chunk", [1, CHUNK])
def test_paged_mla_kernel_compiles_for_v5e(one_chip, chunk):
    from repro.kernels import paged_mla
    lanes = ((MLA_LANES,), jnp.int32)
    text = _compile(
        lambda ql, qr, pg, sc, pt, qs, nn, kl: paged_mla.paged_mla_attention(
            ql, qr, pg, sc, pt, qs, nn, kl, sm=192 ** -0.5),
        one_chip,
        ((MLA_LANES, chunk, MLA_H, MLA_R), jnp.bfloat16),
        ((MLA_LANES, chunk, MLA_H, MLA_P), jnp.bfloat16),
        ((MLA_PAGES, 1, PAGE, 640), jnp.int8),
        ((MLA_PAGES, 1), jnp.float32),
        ((MLA_LANES, MLA_COLS), jnp.int32), lanes, lanes, lanes)
    assert ops.compiled_kernels(text) == {"paged_mla_attention"}


@pytest.mark.parametrize("tokens", [MLA_LANES, MLA_LANES * CHUNK])
@pytest.mark.parametrize("k, n", [(HIDDEN, 2 * EXPERT_FF),
                                  (EXPERT_FF, HIDDEN)])
def test_grouped_gemm_compiles_for_v5e(one_chip, tokens, k, n):
    rows = tokens * TOP_K
    text = _compile(
        lambda x, w, a, b, g: ops.grouped_int8_matmul(
            x, w, a, b, g, out_dtype=jnp.bfloat16, impl="pallas"),
        one_chip, ((rows, k), jnp.int8), ((HELD, k, n), jnp.int8),
        ((rows, 1), jnp.float32), ((HELD, 1, n), jnp.float32),
        ((HELD,), jnp.int32))
    assert ops.compiled_kernels(text) == {"grouped_int8_matmul"}


# -- the step programs write the pools in place --------------------------------

_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%([\w.-]+)\s*=\s*(.*?)\s([a-z][\w-]*)"
                    r"\(([^)]*)\)(.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.-]+)\s.*\{$")
_ALIAS = re.compile(r"\{(\d+)\}:\s*\((\d+),")
_VIEWS = ("parameter", "get-tuple-element", "bitcast", "tuple", "while")
_HLO_DTYPES = {"int8": "s8", "uint8": "u8", "bfloat16": "bf16"}


def _instructions(text):
    """Each instruction of a compiled module's text: {name: (the arrays of
    its result as (element type, dims), opcode, operand names,
    attributes)}, and {computation: its root's name}."""
    ins, roots, comp = {}, {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        root, name, shape, op, operands, attrs = m.groups()
        arrays = [(t, tuple(int(d) for d in dims.split(",") if d))
                  for t, dims in _ARRAY.findall(shape)]
        ins[name] = (arrays, op, re.findall(r"%([\w.-]+)", operands), attrs)
        if root:
            roots[comp] = name
    return ins, roots


def _pool_sized_copies(text, pages):
    """The instructions that make an array of pages (the pool's element
    type, its last two dims a page's) as large as one layer's pages or
    larger, other than views of a buffer (parameters, tuple elements,
    bitcasts, the loop) and writes of a few pages into one in place (a
    scatter or dynamic-update-slice, alone or as a fusion's root, whose
    other operands are small). `pages`: one pool leaf (L, N, ...)."""
    ins, roots = _instructions(text)
    dtype, tile = _HLO_DTYPES[pages.dtype.name], tuple(pages.shape[-2:])
    layer = math.prod(pages.shape[1:])

    def big(name):
        return any(t == dtype and math.prod(dims) >= layer
                   and tuple(d for d in dims if d != 1)[-2:] == tile
                   for t, dims in ins[name][0])

    def in_place(name):
        _, op, operands, _ = ins[name]
        return (op in ("scatter", "dynamic-update-slice")
                and not any(big(o) for o in operands[1:]))

    out = []
    for name, (_, op, _, attrs) in ins.items():
        if op in _VIEWS or not big(name):
            continue
        if op == "fusion":
            callee = re.search(r"calls=%([\w.-]+)", attrs).group(1)
            if in_place(roots[callee]):
                continue
        elif in_place(name):
            continue
        out.append(name)
    return out


# The two cells' engines: config, lanes, max_seq_len, pages, token budget.
def _cell(geometry):
    if geometry == "pangu-1b":
        # bench/configs/pangu-1b-w8a8.json's widths, two of its 26 layers
        cfg = dataclasses.replace(get_arch("pangu_1b"), n_layers=2,
                                  n_kv_heads=NKV, d_ff=6144)
        return cfg, LANES, 4624, 4625, 2048
    # bench/configs/pangu-ultra-moe-w8a8.json's block: its leading dense
    # layer and two expert layers, its 8 held experts
    base = get_arch("pangu_ultra_moe")
    cfg = dataclasses.replace(
        base, n_layers=3, first_k_dense=1, vocab=19200, tie_embeddings=False,
        moe=dataclasses.replace(base.moe, held=(0, HELD)))
    return cfg, MLA_LANES, 10256, MLA_PAGES, 8192


def _programs(geometry):
    """Each step program of a cell's engine by name: "decode" and "mixed"
    at each size of the row axis (`engine.mixed_row_sizes`)."""
    from repro.serving.engine import mixed_row_sizes
    _, lanes, _, _, budget = _cell(geometry)
    return ["decode"] + [f"mixed{t}" for t in
                         mixed_row_sizes(CHUNK, lanes, budget)]


STEP_PROGRAMS = [(g, p) for g in ("pangu-1b", "pangu-ultra-moe")
                 for p in _programs(g)]


def _step_program(one_chip, geometry, program):
    """One of the engine's step programs as a cell serves it (W8A8, int8
    pages of 16, the pools donated), compiled for one v5e chip at the
    cell's page count: (compiled text, params, pools) with params and pools
    as shapes. The engine itself holds a two-page pool and compiles nothing
    when built here; its jitted programs take the pools' shapes from their
    arguments."""
    from repro.core.quant import ptq
    from repro.core.quant.qtypes import preset
    from repro.models import transformer
    from repro.serving.engine import ContinuousBatchingEngine
    cfg, lanes, max_seq_len, n_pages, token_budget = _cell(geometry)
    qcfg = preset("int8")
    params = jax.eval_shape(lambda k: ptq.quantize_model(
        transformer.init_params(k, cfg), cfg, qcfg), jax.random.PRNGKey(0))
    pools = jax.eval_shape(
        lambda: transformer.init_paged_pools(cfg, n_pages, PAGE, 8))
    on_chip = lambda tree: jax.tree.map(                    # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    resolve = ops.resolve_impl
    with pytest.MonkeyPatch.context() as mp:
        # "auto" takes the kernels, as it does on the chip
        mp.setattr(ops, "resolve_impl", lambda impl: "pallas"
                   if (impl or "auto") == "auto" else resolve(impl))
        mp.setattr(ContinuousBatchingEngine, "_compile_steps",
                   lambda self: None)
        eng = ContinuousBatchingEngine(
            params, cfg, qcfg=qcfg, kv_bits=8, page_size=PAGE,
            max_batch=lanes, max_seq_len=max_seq_len, n_pages=2,
            chunk_pages=CHUNK // PAGE, token_budget=token_budget)
        if program == "decode":
            prog, args = eng._decode, eng._decode_args()
        else:
            t = int(program[len("mixed"):])
            prog = eng._mixed
            args = tuple(map(jnp.asarray, eng._mixed_args([], [], t)))
        text = prog.lower(on_chip(params), on_chip(pools),
                          *on_chip(args)).compile().as_text()
    return text, params, pools


@pytest.mark.parametrize("geometry, program", STEP_PROGRAMS)
def test_step_programs_write_the_pools_in_place(one_chip, geometry, program):
    """The decode program and the mixed program at every size of its row
    axis, at a cell's page count, make no copy of a pool stack or of one
    layer's pool: no whole-pool copy, no layer's pool sliced out of its
    stack or written back into it, only the pages a step writes, in place;
    and every pool buffer passed in is the buffer that comes out. Two
    layers in a scanned stack, the fewest at which the scan stays a loop
    (a one-layer scan compiles to straight-line code)."""
    text, params, pools = _step_program(one_chip, geometry, program)
    n_params = len(jax.tree.leaves(params))
    n_pools = len(jax.tree.leaves(pools))
    for stack in pools.values():
        assert _pool_sized_copies(text, stack["k"]) == [], program
    aliased = {int(o): int(p)
               for o, p in _ALIAS.findall(text.split("\n", 1)[0])}
    # outputs (logits, pools, counters); arguments (params, pools, ...)
    assert aliased == {1 + i: n_params + i for i in range(n_pools)}, program
