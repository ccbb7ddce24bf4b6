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

Two options shape the stack around the pattern: `cfg.first_k_dense`
leading dense layers ("self" blocks with an MLP of `cfg.dense_width`, kept
under params["dense"] and scanned on their own before the groups), and
`cfg.sandwich_norm`, under which each sublayer's output is normed before
its residual add:

    h = x + N_post(Attn(N_pre(x))),   y = h + N_post'(FFN(N_pre'(h))).

With `cfg.mla`, every attention block is latent attention
(models/attention.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

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


def _sandwich(p: dict, cfg) -> dict:
    """The post-sublayer norms of a block, when the config has them."""
    if not cfg.sandwich_norm:
        return p
    return dict(p, ln1_post=init_rms_norm(cfg.d_model),
                ln2_post=init_rms_norm(cfg.d_model))


def _post(p, name: str, y, cfg):
    """A sublayer's output before its residual add: normed by `name` under
    sandwich norms, else as it is."""
    return rms_norm(y, p[name]["g"], cfg.norm_eps) if cfg.sandwich_norm \
        else y


def _no_dense_cache(cfg):
    if cfg.mla is not None:
        raise NotImplementedError(
            f"{cfg.name}: latent attention runs on the paged path only")


# -- self-attention + MLP ----------------------------------------------------

def _init_self(key, cfg, ff: Optional[int] = None):
    k1, k2 = jax.random.split(key)
    return _sandwich({"ln1": init_rms_norm(cfg.d_model),
                      "attn": attn.init_attention(k1, cfg),
                      "ln2": init_rms_norm(cfg.d_model),
                      "mlp": init_mlp(k2, cfg.d_model, ff or cfg.d_ff,
                                      cfg.act)}, cfg)


def _self_train(p, x, cfg, env):
    taps, pre = env.get("taps"), env.get("prefix", "")
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    a, kv = attn.attn_forward(p["attn"], h, cfg, env["positions"],
                              lengths=env.get("lengths"), taps=taps,
                              tap_prefix=pre, **_env_kw(env))
    x = x + _post(p, "ln1_post", a, cfg)
    h = rms_norm(x, p["ln2"]["g"], cfg.norm_eps)
    x = x + _post(p, "ln2_post", mlp(p["mlp"], h, cfg.act, env.get("qcfg"),
                                     env.get("impl"), taps, pre), cfg)
    cache = None
    if env.get("build_cache"):
        _no_dense_cache(cfg)
        cache = attn.init_kv_cache(cfg, x.shape[0], env["max_len"],
                                   env.get("kv_bits", 16))
        cache = attn.cache_write_prefill(cache, *kv)
    return x, cache, 0.0


def _self_decode(p, x, cfg, cache, env):
    _no_dense_cache(cfg)
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
    return _sandwich({"ln1": init_rms_norm(cfg.d_model),
                      "attn": attn.init_attention(k1, cfg),
                      "ln2": init_rms_norm(cfg.d_model),
                      "moe": moe_mod.init_moe(k2, cfg)}, cfg)


def _moe_train(p, x, cfg, env):
    taps, pre = env.get("taps"), env.get("prefix", "")
    h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
    a, kv = attn.attn_forward(p["attn"], h, cfg, env["positions"],
                              lengths=env.get("lengths"), taps=taps,
                              tap_prefix=pre, **_env_kw(env))
    x = x + _post(p, "ln1_post", a, cfg)
    h = rms_norm(x, p["ln2"]["g"], cfg.norm_eps)
    m, aux = moe_mod.moe_ffn(p["moe"], h, cfg, env.get("qcfg"),
                             env.get("impl"), taps, pre,
                             constraint=env.get("moe_sharding"))
    x = x + _post(p, "ln2_post", m, cfg)
    cache = None
    if env.get("build_cache"):
        _no_dense_cache(cfg)
        cache = attn.init_kv_cache(cfg, x.shape[0], env["max_len"],
                                   env.get("kv_bits", 16))
        cache = attn.cache_write_prefill(cache, *kv)
    return x, cache, aux


def _moe_decode(p, x, cfg, cache, env):
    _no_dense_cache(cfg)
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


# Latent attention's quantized linears in place of [q|k|v] and o.
MLA_SITES = {"attn_in": ["attn/wq_a", "attn/wkv_a"],
             "attn_q_in": ["attn/wq_b"], "attn_out": ["attn/wo"]}


def quant_sites(btype: str, cfg) -> Dict[str, list]:
    """The quantized linears of a block of this config, keyed by the tap
    (calibration site) whose activations feed them: the block's own, with
    latent attention's in place of GQA's and the shared expert's added."""
    sites = dict(BLOCKS[btype].quant_sites)
    if cfg.mla is not None and btype in ("self", "moe"):
        sites = {**MLA_SITES, **{t: v for t, v in sites.items()
                                 if not t.startswith("attn_")}}
    if btype == "moe" and cfg.moe.n_shared:
        sites["shared_mlp_in"] = ["moe/shared/w_in"]
        sites["shared_mlp_out"] = ["moe/shared/w_out"]
    return sites


def stacks(cfg):
    """(params / pools key, block type) of each scanned stack, in layer
    order: the leading dense layers, then each position of the pattern."""
    lead = [("dense", "self")] if cfg.first_k_dense else []
    return lead + [(str(i), b) for i, b in enumerate(cfg.pattern)]


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
    if cfg.first_k_dense:
        dk = jax.random.split(jax.random.fold_in(keys[-3], 1),
                              cfg.first_k_dense)
        params["dense"] = jax.vmap(
            lambda k: _init_self(k, cfg, cfg.dense_width))(dk)
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
    taps = {}
    if cfg.first_k_dense:
        def dense_body(x, p):
            t = Taps(collect_taps)
            env = {"positions": positions, "lengths": batch.get("lengths"),
                   "qcfg": qcfg, "impl": impl, "taps": t,
                   "prefix": "dense/"}
            x, _, _ = _self_train(p, x, cfg, env)
            return x, t.data

        x, taps = jax.lax.scan(dense_body, x, params["dense"])
    x, ys = jax.lax.scan(body, x, params["blocks"])
    ys["taps"] = {**taps, **ys["taps"]}
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
    _no_dense_cache(cfg)
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
    _no_dense_cache(cfg)
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
    """Per-block page pools stacked (G, N, ...) over the groups (mirrors
    init_caches), and (first_k_dense, N, ...) ones under "dense" for the
    leading dense layers; the step programs view each stack as one pool of
    G*N pages (`_paged_layers`). kv_bits selects the pool dtype —
    16 (dense `dtype`), 8 (int8 + per-(page, head) scales) or 4 (uint8
    nibble-packed int4, head_dim halved in storage); every jitted step
    below reads the pool dtype back off the leaves, so the same step
    functions serve all three."""
    from repro.serving import kv_pool   # serving imports models at init
    assert supports_paged(cfg), \
        f"paged decode supports patterns {PAGED_PATTERNS}, full attention"
    pools = {}
    for name, _ in stacks(cfg):
        n = cfg.first_k_dense if name == "dense" else cfg.n_groups
        one = kv_pool.init_pool(cfg, n_pages, page_size, kv_bits, dtype)
        pools[name] = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (n,) + l.shape), one)
    return pools


# The paged step programs carry named scopes (embed; attention, with
# kv_write and paged_attention inside it, and mla_absorb under latent
# attention; mlp, with router, experts and shared_expert inside it in a
# layer of experts; lm_head), so that a profile or a compiled program's op
# metadata names the part of the step an op belongs to. Scopes are
# metadata only: they change no op.

def _paged_embed(params, tokens, dtype):
    with jax.named_scope("embed"):
        return params["embed"]["w"].astype(dtype)[tokens]


def _paged_block(p, x, pool, off, btype, cfg, attend, qcfg, impl, valid):
    """One block of a paged step: (x, what `attend` returns beside its
    output, the expert layer's counters or None)."""
    with jax.named_scope("attention"):
        h = rms_norm(x, p["ln1"]["g"], cfg.norm_eps)
        a, r = attend(p["attn"], h, pool, off)
        a = _post(p, "ln1_post", a, cfg)
    x = x + a
    stats = None
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["ln2"]["g"], cfg.norm_eps)
        if btype == "moe":
            m, stats = moe_mod.dropless_moe(p["moe"], h, cfg, qcfg, impl,
                                            valid=valid)
        else:
            m = mlp(p["mlp"], h, cfg.act, qcfg, impl)
        m = _post(p, "ln2_post", m, cfg)
    x = x + m
    return x, r, stats


def _flat_pool(stack: dict) -> dict:
    """A stack's pools (L, N, ...) viewed as one pool of L*N pages (a
    bitcast): layer l's page p is page l*N + p."""
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), stack)


def _paged_layers(params, pools, x, cfg, attend, qcfg, impl, valid=None,
                  writes: bool = True):
    """The blocks of a paged step: the leading dense layers, then the
    pattern scanned over groups. Each pool stack (L, N, ...) rides its scan
    in the carry as one flat pool of L*N pages (`_flat_pool`), so a layer
    writes its pages in place and no layer's pool is sliced out of the
    stack or written back into it. `attend(p, h, pool, off)` is the step's
    attention over that flat pool, the layer's page p at p + off (off =
    l*N); with `writes` it returns (out, the written pool), else (out, aux)
    and the pools are only read. `valid` (x's leading shape) marks the
    real tokens (the expert layer routes the others nowhere). Returns (x,
    the pools as given (L, N, ...), each stack's aux stacked over its
    layers (read-only steps), the expert layers' counters: {"expert_rows":
    (layers, E_held), "expert_absent": (layers,)}, or {} without a layer
    of experts)."""
    new_pools, aux = {}, {}

    def scan(x, ps, btypes):
        """Scan the stacks `btypes` ({key: block type}) of one layer
        stack's params `ps` ({key: (L, ...)})."""
        n_layers, n = pools[next(iter(btypes))]["k"].shape[:2]

        def body(carry, scanned):
            (x, flat), (p, off) = carry, scanned
            flat, out, stats = dict(flat), {}, {}
            for k, btype in btypes.items():
                x, r, st = _paged_block(p[k], x, flat[k], off, btype, cfg,
                                        attend, qcfg, impl, valid)
                if writes:
                    flat[k] = r
                else:
                    out[k] = r
                if st is not None:
                    stats[k] = st
            return (x, flat), (out, stats)

        flat = {k: _flat_pool(pools[k]) for k in btypes}
        offs = jnp.arange(n_layers, dtype=jnp.int32) * n
        (x, flat), (out, stats) = jax.lax.scan(body, (x, flat), (ps, offs))
        for k in btypes:
            new_pools[k] = jax.tree.map(lambda a, b: a.reshape(b.shape),
                                        flat[k], pools[k])
        aux.update(out)
        return x, stats

    if cfg.first_k_dense:
        x, _ = scan(x, {"dense": params["dense"]}, {"dense": "self"})
    x, stats = scan(x, params["blocks"],
                    {str(i): b for i, b in enumerate(cfg.pattern)})
    counters = {}
    if stats:             # (G, positions) -> layers in order
        moe_pos = sorted(stats, key=int)
        rows = jnp.stack([stats[i]["rows"] for i in moe_pos], axis=1)
        counters = {
            "expert_rows": rows.reshape(-1, rows.shape[-1]),
            "expert_absent": jnp.stack([stats[i]["absent"]
                                        for i in moe_pos], 1).reshape(-1)}
    return x, new_pools, aux, counters


def _paged_head(params, x, cfg):
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"]["g"], cfg.norm_eps)
        return _lm_logits(params, x, cfg)


def decode_step_paged(params, pools, page_table, tokens, pos, cfg, *,
                      qcfg=None, impl=None, paged_impl=None,
                      dtype=jnp.bfloat16, with_stats: bool = False):
    """One decode step against paged KV pools. tokens: (B,) int32; pos: (B,)
    absolute write positions (inactive slots: 0 with a scratch page-table
    row; a working lane holds at least its prompt, so pos > 0). Returns
    (logits (B,V) f32, pools), and with `with_stats` the expert layers'
    counters (`_paged_layers`) third."""
    x = _paged_embed(params, tokens, dtype)[:, None, :]
    if cfg.mla is not None:
        def attend(p, h, pool, off):
            return attn.attn_mla_paged(p, h, cfg, pool, page_table + off,
                                       pos, qcfg=qcfg, impl=impl,
                                       paged_impl=paged_impl)
    else:
        def attend(p, h, pool, off):
            return attn.attn_decode_paged(p, h, cfg, pool, page_table + off,
                                          pos, qcfg=qcfg, impl=impl,
                                          paged_impl=paged_impl)
    x, new_pools, _, counters = _paged_layers(params, pools, x, cfg, attend,
                                              qcfg, impl, (pos > 0)[:, None])
    logits = _paged_head(params, x, cfg)[:, 0]
    if with_stats:
        return logits, new_pools, counters
    return logits, new_pools


def pack_rows(tokens, q_start, n_new, t: int, order=None):
    """Lay a mixed step's real tokens on one row axis of `t` rows, on the
    host. tokens (B, C): lane b's tokens in columns [0, n_new[b]), from
    absolute position q_start[b]; order: the lanes in the order their
    tokens take the rows (by default every lane with a token, in lane
    order). Returns numpy (tokens (T,), pos (T,), lane (T,), grid (B, C))
    as `prefill_chunk_paged` takes them: padding rows hold token 0 at
    position 0 in lane B, and an empty slot of the grid names row T."""
    tokens, q_start, n_new = (np.asarray(a, np.int32)
                              for a in (tokens, q_start, n_new))
    b, c = tokens.shape
    order = np.flatnonzero(n_new) if order is None else np.asarray(
        order, np.int64)
    n = n_new[order]
    lane = np.repeat(order, n)                                 # real rows
    col = np.arange(lane.size) - np.repeat(np.cumsum(n) - n, n)
    assert lane.size <= t, (lane.size, t)
    packed = np.zeros((3, t), np.int32)                  # tokens, pos, lane
    packed[2] = b
    packed[:, :lane.size] = tokens[lane, col], q_start[lane] + col, lane
    grid = np.full((b, c), t, np.int32)
    grid[lane, col] = np.arange(lane.size)
    return packed[0], packed[1], packed[2], grid


def prefill_chunk_paged(params, pools, page_table, window_rows, tokens, pos,
                        lane, grid, q_start, n_new, cfg, *, qcfg=None,
                        impl=None, paged_impl=None, dtype=jnp.bfloat16,
                        with_stats: bool = False):
    """One mixed chunked-prefill/decode step against paged KV pools — the
    continuous-batching engine's mixed program, one compilation for each
    size T of its row axis.

    The step's real tokens lie packed on one row axis (`pack_rows`):
    tokens, pos, lane (T,) — each row's token, absolute position and lane
    (B for a padding row) — and grid (B, C), the row of each lane's chunk
    slot (T for an empty one). q_start: (B,) absolute position of a lane's
    first new token (== tokens already in cache); n_new: (B,) its real
    tokens (a prefill chunk or part of one, 1 = decode, 0 = idle);
    window_rows: (B, Wc) write-window pages (kv_pool.write_chunk);
    page_table: (B, W) full table for reads.

    The embedding, norms, linears and the expert layer run on the T rows;
    each block's attention gathers its K/V and queries onto the (B, C)
    grid, quantizes the K/V straight into int8 or packed-int4 pages
    (per-(page, head) scales), attends causally over written pages plus
    the in-flight chunk, and brings its output back onto the rows
    (`attention.PackedRows`). Returns (logits (B, V) f32 at each lane's
    last real row, pools), and with `with_stats` the expert layers'
    counters third."""
    rows = attn.PackedRows(pos, lane, grid)
    x = _paged_embed(params, tokens, dtype)                   # (T, d)
    if cfg.mla is not None:
        def attend(p, h, pool, off):
            return attn.attn_mla_chunk_paged(
                p, h, cfg, pool, page_table + off, window_rows + off, rows,
                q_start, n_new, qcfg=qcfg, impl=impl, paged_impl=paged_impl)
    else:
        def attend(p, h, pool, off):
            return attn.attn_prefill_chunk_paged(
                p, h, cfg, pool, page_table + off, window_rows + off, rows,
                q_start, n_new, qcfg=qcfg, impl=impl, paged_impl=paged_impl)
    x, new_pools, _, counters = _paged_layers(params, pools, x, cfg, attend,
                                              qcfg, impl, rows.real)
    b, c = grid.shape
    last = grid[jnp.arange(b), jnp.clip(n_new - 1, 0, c - 1)]
    x = jnp.take(x, last, axis=0, mode="clip")[:, None]      # (B, 1, d)
    logits = _paged_head(params, x, cfg)[:, 0]
    if with_stats:
        return logits, new_pools, counters
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
    window position, kv_win = {block: (k, v) (G, B, C, nkv, hd)}).
    Latent attention has no verify step."""
    assert cfg.mla is None, "no speculative verify under latent attention"
    c = tokens.shape[1]
    x = _paged_embed(params, tokens, dtype)                   # (B, C, d)
    x, _, kv_win, _ = _paged_layers(
        params, pools, x, cfg,
        lambda p, h, pool, off: attn.attn_verify_paged(
            p, h, cfg, pool, page_table + off, q_start, n_new, qcfg=qcfg,
            impl=impl),
        qcfg, impl, jnp.arange(c)[None, :] < n_new[:, None], writes=False)
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
