"""openPangu-Ultra-MoE's block at toy widths on the CPU: latent attention
through the latent pages against the full forward pass, the absorbed paged
kernel and the grouped expert GEMM against their oracles, the dropless
expert layer (a token's output does not depend on its batch; the shares of
the held experts add up to the uncut layer), and the published config's
parameter counts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (MIXED_LANES, make_engine, packed_mixed_step,
                      pages_changed)
from repro.configs import get_arch, reduced
from repro.core.quant.qtypes import INT8
from repro.core.quant import ptq
from repro.kernels import ops, paged_mla
from repro.models import moe as moe_mod
from repro.models import transformer
from repro.serving import kv_pool

PROMPTS = [list(range(1, 20)), [9] * 17, [3, 1, 4, 1, 5, 9, 2, 6]]


@pytest.fixture(scope="module")
def ultra():
    cfg = reduced(get_arch("pangu_ultra_moe"))
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_the_published_config_counts_718b_and_39b_active():
    cfg = get_arch("pangu-ultra-moe")
    assert cfg.n_layers == 61 and cfg.first_k_dense == 3
    assert cfg.n_groups == 58 and cfg.moe.num_experts == 256
    assert cfg.vocab == 153600 and cfg.mla.latent_dim == 576
    # 196,575,232 attention parameters a layer (q_a, q_b, kv_a, kv_b, o)
    assert cfg.attn_param_count() == 196575232
    assert 715e9 < cfg.param_count() < 722e9
    # 39-40B active: the published A39B, within the embedding and head
    assert 39e9 < cfg.active_param_count() < 40.5e9


@pytest.mark.parametrize("rows", [0, 3])
def test_paged_latent_steps_match_the_full_forward(ultra, kv_bits, rows):
    """A prompt written through the mixed step (a chunk, then `rows`
    decode steps through the latent pages) gives the full forward pass's
    logits at each position: as closely as bf16 pages hold the latent,
    within a page scale's rounding for int8 and int4 pages. Every expert is
    chosen here, so that no near-tie of the router turns a rounding into
    another expert."""
    cfg, params = ultra
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, top_k=cfg.moe.num_experts))
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 24 + rows), 0,
                              cfg.vocab - 8)
    full, _ = transformer.forward_train(params, {"tokens": toks}, cfg,
                                        remat=False)
    page, c, n_pages = 8, 32, 9
    pools = transformer.init_paged_pools(cfg, n_pages, page, kv_bits)
    table = jnp.arange(1, n_pages, dtype=jnp.int32)[None]
    chunk = jnp.zeros((1, c), jnp.int32).at[0, :24].set(toks[0, :24])
    q_start, n_new = jnp.zeros(1, jnp.int32), jnp.full((1,), 24, jnp.int32)
    packed = transformer.pack_rows(chunk, q_start, n_new, c)
    logits, pools = transformer.prefill_chunk_paged(
        params, pools, table, table[:, :5], *packed, q_start, n_new, cfg)
    got = [logits[0]]
    for i in range(rows):
        pos = jnp.full((1,), 24 + i, jnp.int32)
        logits, pools = transformer.decode_step_paged(
            params, pools, table, toks[:, 24 + i], pos, cfg)
        got.append(logits[0])
    want = full[0, 23:24 + rows]
    tol = {16: 0.03, 8: 0.06, 4: 0.35}[kv_bits]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(jnp.stack(got)), np.asarray(want),
                               atol=tol * scale)


@pytest.mark.parametrize("t", [40, 80])     # tight, and the lanes' grid
def test_packed_mixed_step_matches_the_full_forward(ultra, kv_bits, t):
    """Latent attention and the expert layer on the packed rows: two
    prompts at different chunk offsets (one a partial chunk), two decode
    lanes and an idle lane in one mixed step. Each working lane's logits
    are the full forward pass's at its last token; the expert counters
    hold the real tokens' pairs only (padding rows route nowhere); no page
    outside the working lanes' write windows (and the scratch page the
    idle lane writes) changed. Every expert is chosen, as above."""
    cfg, params = ultra
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, top_k=cfg.moe.num_experts))
    got = packed_mixed_step(cfg, params, kv_bits, t)
    tol = {16: 0.03, 8: 0.06, 4: 0.35}[kv_bits]
    for lane, (_, n) in enumerate(MIXED_LANES):
        if n:
            want = got["want"][lane]
            np.testing.assert_allclose(
                got["logits"][lane, :cfg.vocab], want[:cfg.vocab],
                atol=tol * np.abs(want).max(), err_msg=f"lane {lane}")
    rows = np.asarray(got["counters"]["expert_rows"])
    absent = np.asarray(got["counters"]["expert_absent"])
    assert rows.shape[0] == absent.shape[0] == cfg.n_groups
    np.testing.assert_array_equal(rows.sum(-1) + absent,
                                  got["tokens"] * cfg.moe.top_k)
    written = set(got["windows"].reshape(-1).tolist()) | {
        kv_pool.SCRATCH_PAGE}
    assert pages_changed(got["before"], got["after"]) <= written


def test_the_engine_serves_it_with_expert_counters(ultra, kv_bits):
    cfg, params = ultra
    eng = make_engine(params, cfg, kv_bits=kv_bits)
    res = eng.run(PROMPTS, mode="no_think", max_new=12)
    assert all(len(t) == 3 for t in res.tokens)
    steps = [r for r in eng.telemetry.steps if r.kind != "idle"]
    for r in steps:
        assert len(r.expert_rows) == cfg.n_groups
        # every held pair of the step's real tokens, none of its padding
        held = sum(map(sum, r.expert_rows)) + sum(r.expert_absent)
        assert held == r.tokens * cfg.moe.top_k


def test_latent_attention_refuses_what_it_cannot_serve(ultra):
    cfg, params = ultra
    with pytest.raises(AssertionError, match="latent attention"):
        make_engine(params, cfg, spec_decode=2)
    with pytest.raises(NotImplementedError, match="paged path"):
        transformer.prefill(params, {"tokens": jnp.zeros((1, 4), int)}, cfg,
                            max_len=8)


@pytest.mark.parametrize("quantized", [True, False])
def test_paged_mla_kernel_matches_its_oracle(quantized):
    """Ragged lanes of a mixed step: a full chunk, a decode lane, a partial
    chunk and an idle lane; only the rows of real tokens are compared (the
    kernel skips the query blocks past them)."""
    rng = np.random.default_rng(0)
    b, c, h, r, p, page, w = 4, 8, 4, 128, 64, 8, 6
    n_pool = 1 + b * w
    lat = rng.normal(size=(n_pool, 1, page, 256)).astype(np.float32)
    lat[..., r + p:] = 0.0
    if quantized:
        pages = jnp.asarray(np.clip(np.round(lat * 40), -127, 127), jnp.int8)
        scales = jnp.asarray(rng.uniform(0.01, 0.05, (n_pool, 1)),
                             jnp.float32)
    else:
        pages, scales = jnp.asarray(lat, jnp.bfloat16), None
    table = jnp.asarray(np.arange(1, n_pool).reshape(b, w), jnp.int32)
    q_start = jnp.asarray([0, 13, 30, 0], jnp.int32)
    n_new = jnp.asarray([8, 1, 3, 0], jnp.int32)
    kv_len = jnp.maximum(q_start + n_new, 1)
    q_lat = jnp.asarray(rng.normal(size=(b, c, h, r)), jnp.bfloat16)
    q_rope = jnp.asarray(rng.normal(size=(b, c, h, p)), jnp.bfloat16)
    sm = 192 ** -0.5
    got = paged_mla.paged_mla_attention(q_lat, q_rope, pages, scales, table,
                                        q_start, n_new, kv_len, sm=sm,
                                        interpret=True)
    want = paged_mla.paged_mla_attention_ref(q_lat, q_rope, pages, scales,
                                             table, q_start, kv_len, sm=sm)
    for lane in range(b - 1):              # the idle lane has no real row
        n = int(n_new[lane])
        g = np.asarray(got[lane, :n], np.float32)
        x = np.asarray(want[lane, :n], np.float32)
        # bf16 probabilities into the p . c_kv product, bf16 outputs
        np.testing.assert_allclose(g, x, atol=0.02 * max(1.0, np.abs(x).max()))


def test_grouped_gemm_matches_per_expert_int8_matmul():
    rng = np.random.default_rng(1)
    m, k, n, e = 96, 256, 384, 4
    x = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(-127, 128, (e, k, n)), jnp.int8)
    xs = jnp.asarray(rng.uniform(0.01, 0.02, (m, 1)), jnp.float32)
    ws = jnp.asarray(rng.uniform(0.01, 0.02, (e, 1, n)), jnp.float32)
    sizes = jnp.asarray([10, 0, 37, 20], jnp.int32)
    got = ops.grouped_int8_matmul(x, w, xs, ws, sizes,
                                  impl="pallas_interpret")
    start = 0
    for g, size in enumerate(np.asarray(sizes)):
        want = ops.int8_matmul(x[start:start + size], w[g],
                               xs[start:start + size], ws[g], impl="xla")
        np.testing.assert_array_equal(np.asarray(got[start:start + size]),
                                      np.asarray(want))
        start += size


def _layer(cfg, seed=3):
    """One MoE layer's expert params (unstacked), float and W8A8 through
    the program's PTQ of its block."""
    block = transformer.BLOCKS["moe"].init(jax.random.PRNGKey(seed), cfg)
    block["moe"]["router"]["bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), (cfg.moe.num_experts,))
    one = dataclasses.replace(cfg, n_layers=1, first_k_dense=0)
    q = ptq.quantize_model({"blocks": {"0": block}}, one, INT8)
    return block["moe"], q["blocks"]["0"]["moe"]


def test_dropless_output_does_not_depend_on_the_batch(ultra):
    cfg, _ = ultra
    _, pq = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, cfg.d_model),
                          jnp.bfloat16)
    alone, _ = moe_mod.dropless_moe(pq, x[:, :3], cfg, INT8, "xla")
    crowd, st = moe_mod.dropless_moe(pq, x, cfg, INT8, "xla")
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(crowd[:, :3]))
    valid = jnp.arange(24)[None] < 3
    masked, sm = moe_mod.dropless_moe(pq, x, cfg, INT8, "xla", valid=valid)
    np.testing.assert_array_equal(np.asarray(alone),
                                  np.asarray(masked[:, :3]))
    assert int(sm["rows"].sum() + sm["absent"]) == 3 * cfg.moe.top_k
    assert int(st["rows"].sum() + st["absent"]) == 24 * cfg.moe.top_k


def test_held_shares_add_up_to_the_uncut_layer(ultra):
    """Four chips holding two experts each of eight: the four partial
    results, with the shared expert (which every chip computes) counted
    once, add up to the layer that holds all eight."""
    cfg, _ = ultra
    m = dataclasses.replace(cfg.moe, num_experts=8, top_k=3, held=None)
    full_cfg = dataclasses.replace(cfg, moe=m)
    p, _ = _layer(full_cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 8, cfg.d_model),
                          jnp.float32)
    whole, _ = moe_mod.dropless_moe(p, x, full_cfg)
    shared = moe_mod.mlp(p["shared"], x.reshape(-1, cfg.d_model), cfg.act)
    parts = jnp.zeros_like(whole)
    rows = 0
    for lo in range(0, 8, 2):
        share = dataclasses.replace(full_cfg, moe=dataclasses.replace(
            m, held=(lo, lo + 2)))
        held = dict(p, w_in={"w": p["w_in"]["w"][lo:lo + 2]},
                    w_out={"w": p["w_out"]["w"][lo:lo + 2]})
        y, st = moe_mod.dropless_moe(held, x, share)
        parts = parts + y - shared.reshape(y.shape)
        rows += int(st["rows"].sum())
    assert rows == 2 * 8 * 3
    np.testing.assert_allclose(np.asarray(parts + shared.reshape(x.shape)),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
