"""Generic decoder stack driven by `ArchConfig.pattern`.

The repeating block pattern is scanned over `n_groups` groups (params
stacked on a leading G axis), which keeps compile time flat in depth for
the 40-cell dry-run matrix. Block registry:

  self    — GQA/SWA attention + MLP            (dense, qwen*, glm4, nemotron)
  moe     — GQA/SWA attention + MoE FFN        (mixtral)
  cross   — cross-attention (image ctx) + MLP  (llama-3.2-vision)
  hybrid  — parallel attention ∥ mamba + MLP   (hymba)
  mlstm / slstm — xLSTM blocks                 (xlstm)

Three lowerable entry points per architecture:
  forward_train(...)  full-sequence logits (+taps/aux) — train_4k
  prefill(...)        full-sequence -> (last logits, caches) — prefill_32k
  decode_step(...)    one token against caches — decode_32k / long_500k

All GEMMs are quantization-aware: pass `qcfg` + PTQ'd params and the same
code runs the INT8/W4A8 kernels.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.quant import qlinear
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models import xlstm as xl
from repro.models.layers import Taps, init_mlp, init_rms_norm, mlp, rms_norm


# ---------------------------------------------------------------------------
# Block registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockDef:
    init: Callable
    train: Callable        # (p, x, cfg, env) -> (x, cache_or_None, aux)
    decode: Callable       # (p, x, cfg, cache, env) -> (x, cache)
    init_cache: Callable   # (cfg, batch, max_len, kv_bits) -> cache
    quant_sites: Dict[str, list]


def _env_kw(env):
    return dict(qcfg=env.get("qcfg"), impl=env.get("impl"))


# -- self-attention + MLP ----------------------------------------------------

def _init_self(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"ln1": init_rms_norm(cfg.d_model),
            "attn": attn.init_attention(k1, cfg),
            "ln2": init_rms_norm(cfg.d_model),
            "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act)}


def _self_train(p, x, cfg, env):
    taps, pre = env.get("taps"), env.get("prefix", "")
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    a, kv = attn.attn_forward(p["attn"], h, cfg, env["positions"],
                              lengths=env.get("lengths"), taps=taps,
                              tap_prefix=pre, **_env_kw(env))
    x = x + a
    h = rms_norm(x, p["ln2"]["g"], cfg.norm_eps)
    x = x + mlp(p["mlp"], h, cfg.act, env.get("qcfg"), env.get("impl"),
                taps, pre)
    cache = None
    if env.get("build_cache"):
        cache = attn.init_kv_cache(cfg, x.shape[0], env["max_len"],
                                   env.get("kv_bits", 16))
        cache = attn.cache_write_prefill(cache, *kv)
    return x, cache, 0.0


def _self_decode(p, x, cfg, cache, env):
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    a, cache = attn.attn_decode(p["attn"], h, cfg, cache, env["pos"],
                                **_env_kw(env))
    x = x + a
    h = rms_norm(x, p["ln2"]["g"], cfg.norm_eps)
    x = x + mlp(p["mlp"], h, cfg.act, env.get("qcfg"), env.get("impl"))
    return x, cache


# -- MoE ----------------------------------------------------------------------

def _init_moe_block(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"ln1": init_rms_norm(cfg.d_model),
            "attn": attn.init_attention(k1, cfg),
            "ln2": init_rms_norm(cfg.d_model),
            "moe": moe_mod.init_moe(k2, cfg)}


def _moe_train(p, x, cfg, env):
    taps, pre = env.get("taps"), env.get("prefix", "")
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    a, kv = attn.attn_forward(p["attn"], h, cfg, env["positions"],
                              lengths=env.get("lengths"), taps=taps,
                              tap_prefix=pre, **_env_kw(env))
    x = x + a
    h = rms_norm(x, p["ln2"]["g"], cfg.norm_eps)
    m, aux = moe_mod.moe_ffn(p["moe"], h, cfg, env.get("qcfg"),
                             env.get("impl"), taps, pre,
                             constraint=env.get("moe_sharding"))
    x = x + m
    cache = None
    if env.get("build_cache"):
        cache = attn.init_kv_cache(cfg, x.shape[0], env["max_len"],
                                   env.get("kv_bits", 16))
        cache = attn.cache_write_prefill(cache, *kv)
    return x, cache, aux


def _moe_decode(p, x, cfg, cache, env):
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    a, cache = attn.attn_decode(p["attn"], h, cfg, cache, env["pos"],
                                **_env_kw(env))
    x = x + a
    h = rms_norm(x, p["ln2"]["g"], cfg.norm_eps)
    m, _ = moe_mod.moe_ffn(p["moe"], h, cfg, env.get("qcfg"), env.get("impl"),
                           constraint=env.get("moe_sharding"))
    return x + m, cache


# -- cross-attention ----------------------------------------------------------

def _init_cross(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"ln1": init_rms_norm(cfg.d_model),
            "attn": attn.init_attention(k1, cfg, cross=True),
            "ln2": init_rms_norm(cfg.d_model),
            "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act)}


def _cross_train(p, x, cfg, env):
    taps, pre = env.get("taps"), env.get("prefix", "")
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    a, kv = attn.attn_forward(p["attn"], h, cfg, None, ctx=env["ctx"],
                              taps=taps, tap_prefix=pre, **_env_kw(env))
    x = x + a
    h = rms_norm(x, p["ln2"]["g"], cfg.norm_eps)
    x = x + mlp(p["mlp"], h, cfg.act, env.get("qcfg"), env.get("impl"),
                taps, pre)
    cache = None
    if env.get("build_cache"):
        cache = attn.init_cross_cache(cfg, x.shape[0], env.get("kv_bits", 16))
        cache = attn.cache_write_prefill(cache, *kv)
    return x, cache, 0.0


def _cross_decode(p, x, cfg, cache, env):
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    a = attn.cross_decode(p["attn"], h, cfg, cache, **_env_kw(env))
    x = x + a
    h = rms_norm(x, p["ln2"]["g"], cfg.norm_eps)
    x = x + mlp(p["mlp"], h, cfg.act, env.get("qcfg"), env.get("impl"))
    return x, cache


# -- hybrid (attention ∥ mamba) ------------------------------------------------

def _init_hybrid(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": init_rms_norm(cfg.d_model),
            "attn": attn.init_attention(k1, cfg),
            "mamba": mb.init_mamba(k2, cfg),
            "norm_a": init_rms_norm(cfg.d_model),
            "norm_m": init_rms_norm(cfg.d_model),
            "ln2": init_rms_norm(cfg.d_model),
            "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.act)}


def _hybrid_train(p, x, cfg, env):
    taps, pre = env.get("taps"), env.get("prefix", "")
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    a, kv = attn.attn_forward(p["attn"], h, cfg, env["positions"],
                              lengths=env.get("lengths"), taps=taps,
                              tap_prefix=pre, **_env_kw(env))
    m, mstate = mb.mamba_forward(p["mamba"], h, cfg, taps=taps,
                                 tap_prefix=pre,
                                 constraint=env.get("mamba_sharding"),
                                 **_env_kw(env))
    fused = 0.5 * (rms_norm(a, p["norm_a"]["g"], cfg.norm_eps)
                   + rms_norm(m, p["norm_m"]["g"], cfg.norm_eps))
    x = x + fused
    h = rms_norm(x, p["ln2"]["g"], cfg.norm_eps)
    x = x + mlp(p["mlp"], h, cfg.act, env.get("qcfg"), env.get("impl"),
                taps, pre)
    cache = None
    if env.get("build_cache"):
        kvc = attn.init_kv_cache(cfg, x.shape[0], env["max_len"],
                                 env.get("kv_bits", 16))
        cache = {"attn": attn.cache_write_prefill(kvc, *kv), "mamba": mstate}
    return x, cache, 0.0


def _hybrid_decode(p, x, cfg, cache, env):
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    a, kvc = attn.attn_decode(p["attn"], h, cfg, cache["attn"], env["pos"],
                              **_env_kw(env))
    m, mstate = mb.mamba_decode(p["mamba"], h, cfg, cache["mamba"],
                                **_env_kw(env))
    fused = 0.5 * (rms_norm(a, p["norm_a"]["g"], cfg.norm_eps)
                   + rms_norm(m, p["norm_m"]["g"], cfg.norm_eps))
    x = x + fused
    h = rms_norm(x, p["ln2"]["g"], cfg.norm_eps)
    x = x + mlp(p["mlp"], h, cfg.act, env.get("qcfg"), env.get("impl"))
    return x, {"attn": kvc, "mamba": mstate}


# -- xLSTM ---------------------------------------------------------------------

def _init_mlstm_block(key, cfg):
    return {"ln1": init_rms_norm(cfg.d_model),
            "cell": xl.init_mlstm(key, cfg)}


def _mlstm_train(p, x, cfg, env):
    taps, pre = env.get("taps"), env.get("prefix", "")
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    y, state = xl.mlstm_parallel(p["cell"], h, cfg, taps=taps,
                                 tap_prefix=pre, **_env_kw(env))
    cache = state if env.get("build_cache") else None
    return x + y, cache, 0.0


def _mlstm_decode(p, x, cfg, cache, env):
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    y, state = xl.mlstm_decode(p["cell"], h, cfg, cache, **_env_kw(env))
    return x + y, state


def _init_slstm_block(key, cfg):
    return {"ln1": init_rms_norm(cfg.d_model),
            "cell": xl.init_slstm(key, cfg)}


def _slstm_train(p, x, cfg, env):
    taps, pre = env.get("taps"), env.get("prefix", "")
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    y, state = xl.slstm_forward(p["cell"], h, cfg, taps=taps,
                                tap_prefix=pre, **_env_kw(env))
    cache = state if env.get("build_cache") else None
    return x + y, cache, 0.0


def _slstm_decode(p, x, cfg, cache, env):
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    y, state = xl.slstm_decode(p["cell"], h, cfg, cache, **_env_kw(env))
    return x + y, state


BLOCKS: Dict[str, BlockDef] = {
    "self": BlockDef(_init_self, _self_train, _self_decode,
                     lambda cfg, b, ml, kv: attn.init_kv_cache(cfg, b, ml, kv),
                     {"attn_in": ["attn/wqkv"], "attn_out": ["attn/wo"],
                      "mlp_in": ["mlp/w_in"], "mlp_out": ["mlp/w_out"]}),
    "moe": BlockDef(_init_moe_block, _moe_train, _moe_decode,
                    lambda cfg, b, ml, kv: attn.init_kv_cache(cfg, b, ml, kv),
                    {"attn_in": ["attn/wqkv"], "attn_out": ["attn/wo"],
                     "mlp_in": ["moe/w_in"], "mlp_out": ["moe/w_out"]}),
    "cross": BlockDef(_init_cross, _cross_train, _cross_decode,
                      lambda cfg, b, ml, kv: attn.init_cross_cache(cfg, b, kv),
                      {"attn_in": ["attn/wq"], "attn_ctx_in": ["attn/wkv"],
                       "attn_out": ["attn/wo"],
                       "mlp_in": ["mlp/w_in"], "mlp_out": ["mlp/w_out"]}),
    "hybrid": BlockDef(_init_hybrid, _hybrid_train, _hybrid_decode,
                       lambda cfg, b, ml, kv: {
                           "attn": attn.init_kv_cache(cfg, b, ml, kv),
                           "mamba": mb.init_mamba_state(cfg, b)},
                       {"attn_in": ["attn/wqkv"], "attn_out": ["attn/wo"],
                        "mamba_in": ["mamba/w_in"],
                        "mamba_out": ["mamba/w_out"],
                        "mlp_in": ["mlp/w_in"], "mlp_out": ["mlp/w_out"]}),
    "mlstm": BlockDef(_init_mlstm_block, _mlstm_train, _mlstm_decode,
                      lambda cfg, b, ml, kv: xl.init_mlstm_state(cfg, b),
                      {"up_in": ["cell/w_up"],
                       "qkv_in": ["cell/w_qkv", "cell/w_if"],
                       "down_in": ["cell/w_down"]}),
    "slstm": BlockDef(_init_slstm_block, _slstm_train, _slstm_decode,
                      lambda cfg, b, ml, kv: xl.init_slstm_state(cfg, b),
                      {"in": ["cell/w_in"], "out": ["cell/w_out"]}),
}


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

def param_dtype():
    """Parameter storage dtype. REPRO_PARAM_DTYPE=bf16 selects mixed-
    precision training (bf16 params + f32 AdamW moments): halves FSDP
    weight-gather AND gradient all-reduce bytes — a §Perf lever."""
    import os
    return (jnp.bfloat16 if os.environ.get("REPRO_PARAM_DTYPE") == "bf16"
            else jnp.float32)


def init_params(key, cfg) -> dict:
    keys = jax.random.split(key, len(cfg.pattern) + 3)
    params: Dict[str, Any] = {}
    dt = param_dtype()
    if cfg.frontend != "embeddings":
        params["embed"] = {"w": (jax.random.normal(
            keys[-1], (cfg.vocab, cfg.d_model), jnp.float32) * 0.02
        ).astype(dt)}
    blocks = {}
    for i, btype in enumerate(cfg.pattern):
        gk = jax.random.split(keys[i], cfg.n_groups)
        blocks[str(i)] = jax.vmap(lambda k: BLOCKS[btype].init(k, cfg))(gk)
    params["blocks"] = blocks
    params["final_norm"] = init_rms_norm(cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = qlinear.init_linear(keys[-2], cfg.d_model,
                                                cfg.vocab)
    if dt != jnp.float32:
        params = jax.tree.map(
            lambda x: x.astype(dt) if x.dtype == jnp.float32 else x, params)
    return params


def _embed_inputs(params, batch, cfg, dtype):
    if cfg.frontend == "embeddings":
        return batch["embeds"].astype(dtype)
    return params["embed"]["w"].astype(dtype)[batch["tokens"]]


def padded_vocab(vocab: int) -> int:
    """LM-head width padded to a TPU/mesh-friendly multiple of 64 (hymba's
    32001-entry vocab otherwise forces replicated (B,S,V) f32 logits —
    30+ GiB/device at train_4k). Padded columns are masked to -1e9."""
    return -(-vocab // 64) * 64


def _lm_logits(params, x, cfg):
    if cfg.tie_embeddings:
        w = params["embed"]["w"].astype(x.dtype).T
    else:
        w = params["lm_head"]["w"].astype(x.dtype)
    vpad = padded_vocab(cfg.vocab)
    if vpad != cfg.vocab:
        w = jnp.pad(w, ((0, 0), (0, vpad - cfg.vocab)))
    logits = (x @ w).astype(jnp.float32)
    if vpad != cfg.vocab:
        mask = jnp.arange(vpad) < cfg.vocab
        logits = jnp.where(mask, logits, -1e9)
    return logits


def forward_train(params, batch, cfg, *, qcfg=None, impl=None,
                  collect_taps: bool = False, remat: bool = True,
                  dtype=jnp.bfloat16, shardings=None):
    """batch: {"tokens": (B,S)} or {"embeds": (B,S,d)}; optional "ctx"
    (B,T,d) image/frame context, "lengths" (B,).
    `shardings`: optional {"act": Sharding, "logits": Sharding} constraints
    (keeps the scan-carry activations and the (B,S,V) f32 logits sharded on
    big meshes — see launch/dryrun.py).
    Returns (logits (B,S,V) f32, aux dict with "taps", "moe_aux")."""
    shardings = shardings or {}
    x = _embed_inputs(params, batch, cfg, dtype)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]
    ctx = batch.get("ctx")
    if ctx is not None:
        ctx = ctx.astype(dtype)

    def body(x, gp):
        taps = Taps(collect_taps)
        aux = 0.0
        for i, btype in enumerate(cfg.pattern):
            env = {"positions": positions, "ctx": ctx,
                   "lengths": batch.get("lengths"), "qcfg": qcfg,
                   "impl": impl, "taps": taps, "prefix": f"{i}/",
                   "moe_sharding": shardings.get("moe"),
                   "mamba_sharding": shardings.get("act")}
            x, _, a = BLOCKS[btype].train(gp[str(i)], x, cfg, env)
            aux = aux + a
        if shardings.get("act") is not None:
            x = jax.lax.with_sharding_constraint(x, shardings["act"])
        return x, {"taps": taps.data, "moe_aux": aux}

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, ys = jax.lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"]["g"], cfg.norm_eps)
    logits = _lm_logits(params, x, cfg)
    if shardings.get("logits") is not None:
        logits = jax.lax.with_sharding_constraint(logits, shardings["logits"])
    aux = {"taps": ys["taps"], "moe_aux": jnp.sum(ys["moe_aux"])}
    return logits, aux


def prefill(params, batch, cfg, *, max_len: int, qcfg=None, impl=None,
            kv_bits: int = 16, dtype=jnp.bfloat16, shardings=None):
    """Run the prompt, build per-layer caches sized `max_len`.
    Returns (logits_last (B,V) f32, caches)."""
    shardings = shardings or {}
    x = _embed_inputs(params, batch, cfg, dtype)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]
    ctx = batch.get("ctx")
    if ctx is not None:
        ctx = ctx.astype(dtype)

    def body(x, gp):
        caches = {}
        for i, btype in enumerate(cfg.pattern):
            env = {"positions": positions, "ctx": ctx,
                   "lengths": batch.get("lengths"), "qcfg": qcfg,
                   "impl": impl, "build_cache": True, "max_len": max_len,
                   "kv_bits": kv_bits, "taps": None, "prefix": "",
                   "moe_sharding": shardings.get("moe"),
                   "mamba_sharding": shardings.get("act")}
            x, cache, _ = BLOCKS[btype].train(gp[str(i)], x, cfg, env)
            caches[str(i)] = cache
        if shardings.get("act") is not None:
            x = jax.lax.with_sharding_constraint(x, shardings["act"])
        return x, caches

    x, caches = jax.lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"]["g"], cfg.norm_eps)
    if "lengths" in batch and batch["lengths"] is not None:
        idx = jnp.maximum(batch["lengths"] - 1, 0)
        x_last = x[jnp.arange(x.shape[0]), idx]
    else:
        x_last = x[:, -1]
    logits = _lm_logits(params, x_last[:, None], cfg)[:, 0]
    return logits, caches


def decode_step(params, caches, token_or_embed, pos, cfg, *, qcfg=None,
                impl=None, dtype=jnp.bfloat16):
    """One decode step. token_or_embed: (B,) int32 tokens or (B,1,d) embeds;
    pos: (B,) absolute positions. Returns (logits (B,V) f32, caches)."""
    if cfg.frontend == "embeddings":
        x = token_or_embed.astype(dtype)
    else:
        x = params["embed"]["w"].astype(dtype)[token_or_embed][:, None, :]

    def body(x, scanned):
        gp, cache = scanned
        new = {}
        for i, btype in enumerate(cfg.pattern):
            env = {"pos": pos, "qcfg": qcfg, "impl": impl}
            x, c = BLOCKS[btype].decode(gp[str(i)], x, cfg, cache[str(i)], env)
            new[str(i)] = c
        return x, new

    x, new_caches = jax.lax.scan(body, x, (params["blocks"], caches))
    x = rms_norm(x, params["final_norm"]["g"], cfg.norm_eps)
    logits = _lm_logits(params, x, cfg)[:, 0]
    return logits, new_caches


# ---------------------------------------------------------------------------
# Paged decode (continuous-batching serving path)
# ---------------------------------------------------------------------------

PAGED_PATTERNS = ("self", "moe")


def supports_paged(cfg) -> bool:
    return (all(b in PAGED_PATTERNS for b in cfg.pattern)
            and cfg.sliding_window == 0 and cfg.frontend == "tokens")


def init_paged_pools(cfg, n_pages: int, page_size: int, kv_bits: int = 16,
                     dtype=jnp.bfloat16) -> dict:
    """Per-block page pools with the (G, ...) stacked structure the decode
    scan expects (mirrors init_caches). kv_bits selects the pool dtype —
    16 (dense `dtype`), 8 (int8 + per-(page, head) scales) or 4 (uint8
    nibble-packed int4, head_dim halved in storage); every jitted step
    below reads the pool dtype back off the leaves, so the same step
    functions serve all three."""
    from repro.serving import kv_pool   # serving imports models at init
    assert supports_paged(cfg), \
        f"paged decode supports patterns {PAGED_PATTERNS}, full attention"
    pools = {}
    for i, _ in enumerate(cfg.pattern):
        one = kv_pool.init_pool(cfg, n_pages, page_size, kv_bits, dtype)
        pools[str(i)] = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (cfg.n_groups,) + l.shape),
            one)
    return pools


# The paged step programs carry named scopes (embed; attention, with
# kv_write and paged_attention inside it; mlp; lm_head), so that a profile
# or a compiled program's op metadata names the part of the step an op
# belongs to. Scopes are metadata only: they change no op.

def _paged_embed(params, tokens, dtype):
    with jax.named_scope("embed"):
        return params["embed"]["w"].astype(dtype)[tokens]


def _paged_layers(params, pools, x, cfg, attend, qcfg, impl):
    """The blocks of a paged step, scanned over groups. `attend(p, h,
    pool)` is the step's attention; it returns (out, aux), and the scan
    returns x and each block's aux."""
    def body(x, scanned):
        gp, gpool = scanned
        new = {}
        for i, btype in enumerate(cfg.pattern):
            p = gp[str(i)]
            with jax.named_scope("attention"):
                h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
                a, new[str(i)] = attend(p["attn"], h, gpool[str(i)])
            x = x + a
            with jax.named_scope("mlp"):
                h = rms_norm(x, p["ln2"]["g"], cfg.norm_eps)
                if btype == "moe":
                    m, _ = moe_mod.moe_ffn(p["moe"], h, cfg, qcfg, impl)
                else:
                    m = mlp(p["mlp"], h, cfg.act, qcfg, impl)
            x = x + m
        return x, new

    return jax.lax.scan(body, x, (params["blocks"], pools))


def _paged_head(params, x, cfg):
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"]["g"], cfg.norm_eps)
        return _lm_logits(params, x, cfg)


def decode_step_paged(params, pools, page_table, tokens, pos, cfg, *,
                      qcfg=None, impl=None, paged_impl=None,
                      dtype=jnp.bfloat16):
    """One decode step against paged KV pools. tokens: (B,) int32; pos: (B,)
    absolute write positions (inactive slots: 0 with a scratch page-table
    row). Returns (logits (B,V) f32, pools)."""
    x = _paged_embed(params, tokens, dtype)[:, None, :]
    x, new_pools = _paged_layers(
        params, pools, x, cfg,
        lambda p, h, pool: attn.attn_decode_paged(
            p, h, cfg, pool, page_table, pos, qcfg=qcfg, impl=impl,
            paged_impl=paged_impl),
        qcfg, impl)
    logits = _paged_head(params, x, cfg)[:, 0]
    return logits, new_pools


def prefill_chunk_paged(params, pools, page_table, window_rows, tokens,
                        q_start, n_new, cfg, *, qcfg=None, impl=None,
                        paged_impl=None, dtype=jnp.bfloat16):
    """One mixed chunked-prefill/decode step against paged KV pools — the
    single steady-state "mixed" compilation of the continuous-batching
    engine (C = chunk width is static; every step has the same shape, so
    decode latency stays flat while long prompts stream in chunks).

    tokens: (B, C) int32 — a prompt chunk for prefilling slots, the last
    sampled token in column 0 for decode slots, zeros for idle slots;
    q_start: (B,) absolute position of chunk token 0 (== tokens already in
    cache); n_new: (B,) valid tokens (C/partial = prefill chunk, 1 =
    decode, 0 = idle); window_rows: (B, Wc) write-window pages
    (kv_pool.write_chunk); page_table: (B, W) full table for reads.

    Each block quantizes the chunk's K/V straight into int8 or packed-int4
    pages (per-(page, head) scales) and attends causally over written pages
    plus the in-flight chunk. Returns (logits (B, V) f32 at each slot's
    last valid token, pools)."""
    c = tokens.shape[1]
    x = _paged_embed(params, tokens, dtype)                   # (B, C, d)
    x, new_pools = _paged_layers(
        params, pools, x, cfg,
        lambda p, h, pool: attn.attn_prefill_chunk_paged(
            p, h, cfg, pool, page_table, window_rows, q_start, n_new,
            qcfg=qcfg, impl=impl, paged_impl=paged_impl),
        qcfg, impl)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"]["g"], cfg.norm_eps)
        last = jnp.clip(n_new - 1, 0, c - 1)
        x_last = x[jnp.arange(x.shape[0]), last]
        logits = _lm_logits(params, x_last[:, None], cfg)[:, 0]
    return logits, new_pools


def verify_step_paged(params, pools, page_table, tokens, q_start, n_new,
                      cfg, *, qcfg=None, impl=None, dtype=jnp.bfloat16):
    """One speculative-verify step: score all C = k+1 positions of each
    sequence's draft window in a single forward (multi-query decode with
    causal masking over the window), *read-only* on the pools.

    tokens: (B, C) int32 — column 0 is the slot's last sampled-but-unwritten
    token, columns 1..n_new-1 are drafter proposals, the rest padding;
    q_start: (B,) tokens already committed to cache; n_new: (B,) window
    tokens (1 = plain decode lane with no draft, 0 = idle).

    The window is scored against the pages plus its own raw in-flight K/V
    (spliced inside the attention read — a rejected draft never touches
    the pool, so there is nothing to roll back), and the raw window
    projections are returned for the engine's commit: the accepted prefix
    goes through the fused quantize-on-write path (`kv_pool.write_chunk`,
    window pages sized by `kv_pool.verify_window_pages` — C unaligned,
    unlike the prefill chunk). Returns (logits (B, C, V) f32 at *every*
    window position, kv_win = {block: (k, v) (G, B, C, nkv, hd)})."""
    x = _paged_embed(params, tokens, dtype)                   # (B, C, d)
    x, kv_win = _paged_layers(
        params, pools, x, cfg,
        lambda p, h, pool: attn.attn_verify_paged(
            p, h, cfg, pool, page_table, q_start, n_new, qcfg=qcfg,
            impl=impl),
        qcfg, impl)
    logits = _paged_head(params, x, cfg)
    return logits, kv_win


def init_caches(params, cfg, batch: int, max_len: int, kv_bits: int = 16):
    """Zero caches with the right per-group stacked structure."""
    caches = {}
    for i, btype in enumerate(cfg.pattern):
        one = BLOCKS[btype].init_cache(cfg, batch, max_len, kv_bits)
        caches[str(i)] = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (cfg.n_groups,) + l.shape),
            one)
    return caches


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def lm_loss(params, batch, cfg, *, qcfg=None, impl=None, dtype=jnp.bfloat16,
            remat: bool = True, shardings=None):
    """Next-token cross-entropy (+ MoE aux + z-loss). batch needs "labels"."""
    logits, aux = forward_train(params, batch, cfg, qcfg=qcfg, impl=impl,
                                remat=remat, dtype=dtype,
                                shardings=shardings)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    zloss = 1e-4 * jnp.mean(jax.scipy.special.logsumexp(logits, -1) ** 2)
    moe_w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
    total = loss + zloss + moe_w * aux["moe_aux"] / max(cfg.n_layers, 1)
    return total, {"nll": loss, "zloss": zloss, "moe_aux": aux["moe_aux"]}
