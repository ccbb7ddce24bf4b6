"""Architecture `dense_gqa`: a dense decoder of identical layers, each
RMSNorm, grouped-query attention with RoPE, RMSNorm, SwiGLU, served by the
program's "self" block.

An architecture is a package `bench/arch/<arch>/` that a configuration
names under its top-level "arch"; `bench.run.load_arch` loads it from the
root the harness runs in. It knows its layer's equations and shapes, and
nothing outside it does. It provides:

  program mapping  arch_config(name, m) -> the program's ArchConfig;
                   make_params(cfg, m, qcfg, seed) -> the served parameter
                   tree, made from the seed in one jitted call on the
                   device and put through the program's PTQ (qcfg None:
                   unquantized)
  reference        forward(x, key, m, p, page, n_real, l0) -> the embedded
                   sequence x (T, d) through every layer, at precision p,
                   with the float32 weights made again from `key`
  counts           attention_layers(m): layers with a paged attention call
                   in each step; gemms(m, tokens): (rows, K, N) of every
                   quantized linear a step makes over all its layers, for
                   `tokens` real tokens (rows may be a share of them, as
                   routing gives); attention_ops_per_key(m): operations of
                   one query against one key in one layer;
                   query_bytes(m): a query read and its output written;
                   kv_bytes_per_token(m, kv_bits), scale_bytes_per_page(m,
                   kv_bits): one layer's cache

`m` is the configuration's `model` section. The weights of the embedding,
the LM head and the final norm, the reference's shared arithmetic and the
per-kernel formulas (`bench/counts/`) are the harness's, for every
architecture.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import weights as shared_weights

from . import weights
from .reference import forward  # noqa: F401  (the interface's reference)

# The program's quantization sites per layer, and the tree it serves from.
_LINEARS = {"wqkv": ("attn", "wqkv"), "wo": ("attn", "wo"),
            "w_in": ("mlp", "w_in"), "w_out": ("mlp", "w_out")}


def arch_config(name: str, m: dict):
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=name, family="dense", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        tie_embeddings=m["tie_word_embeddings"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], act="swiglu")


def _program_layer(w: dict) -> dict:
    tree = {"ln1": {"g": w["ln1"]}, "ln2": {"g": w["ln2"]},
            "attn": {}, "mlp": {}}
    for name, (part, leaf) in _LINEARS.items():
        tree[part][leaf] = {"w": w[name]}
    return tree


def make_params(cfg, m: dict, qcfg, seed: int):
    """The float32 layers come from `weights.layer` one at a time inside
    the call and go straight through the program's PTQ, so the float32
    copy of the blocks is never whole on the chip."""
    from repro.core.quant import ptq
    one = dataclasses.replace(cfg, n_layers=1)

    def quantized_layer(key, i):
        tree = _program_layer(weights.layer(key, i, m))
        if qcfg is None:
            return tree
        return ptq.quantize_model({"blocks": {"0": tree}}, one, qcfg)[
            "blocks"]["0"]

    @jax.jit
    def build(key):
        blocks = jax.lax.map(lambda i: quantized_layer(key, i),
                             jnp.arange(cfg.n_layers))
        params = {"embed": {"w": shared_weights.embed(key, m)},
                  "blocks": {"0": blocks},
                  "final_norm": {"g": shared_weights.final_norm(key, m)}}
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": shared_weights.lm_head(key, m)}
        return params

    return build(shared_weights.base_key(seed))


# -- counts -------------------------------------------------------------------

def _sites(m: dict):
    """(K, N) of each quantized linear of one layer: [q|k|v], o,
    [gate|up], down."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    nq, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    return [(d, (nq + 2 * nkv) * hd), (nq * hd, d), (d, 2 * ff), (ff, d)]


def attention_layers(m: dict) -> int:
    return m["num_hidden_layers"]


def gemms(m: dict, tokens):
    return [(tokens, k, n) for k, n in _sites(m)] * m["num_hidden_layers"]


def attention_ops_per_key(m: dict) -> int:
    """q.k and p.v: 2 hd multiply-adds each, for every query head."""
    return 4 * m["num_attention_heads"] * m["head_dim"]


def query_bytes(m: dict) -> int:
    """The query read and the output written, bf16."""
    return 2 * 2 * m["num_attention_heads"] * m["head_dim"]


def kv_bytes_per_token(m: dict, kv_bits: int) -> float:
    """Bytes of keys and values one token takes in one layer's pages."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * kv_bits / 8


def scale_bytes_per_page(m: dict, kv_bits: int) -> int:
    """Per-(page, kv head) f32 scales of keys and values, for one layer."""
    return 0 if kv_bits == 16 else 2 * m["num_key_value_heads"] * 4
