"""Seeded weights of a dense GQA decoder, made on the device from the seed.

One generator serves both sides of the comparison that decides `correct`:
the adapter hands its float32 output to the program's post-training
quantization, and the plain reference makes the same float32 weights again,
one layer at a time, after the program's state is freed. Nothing here
imports the program.

Sizes come from a configuration file (`bench/configs/<name>.json`):
hidden_size, num_hidden_layers, num_attention_heads, num_key_value_heads,
head_dim, intermediate_size, vocab_size, tie_word_embeddings.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EMBED_STD = 0.02          # token embedding (and tied LM head)
NORM_GAIN_STD = 0.1       # RMSNorm gains are 1 + N(0, 0.1^2)


def base_key(seed: int):
    """A PRNG key from any non-negative seed, 64 bits of it."""
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def layer(key, i, m: dict) -> dict:
    """Layer `i` (an int or a traced scalar): RMSNorm gains and the fused
    projections [q | k | v] (d, (nq + 2 nkv) hd), o (nq hd, d),
    [gate | up] (d, 2 ff) and down (ff, d), each N(0, 1/fan_in)."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    nq, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    k = jax.random.split(jax.random.fold_in(key, 1000 + i), 6)
    return {
        "ln1": 1.0 + _normal(k[0], (d,), NORM_GAIN_STD),
        "wqkv": _normal(k[1], (d, (nq + 2 * nkv) * hd), d ** -0.5),
        "wo": _normal(k[2], (nq * hd, d), (nq * hd) ** -0.5),
        "ln2": 1.0 + _normal(k[3], (d,), NORM_GAIN_STD),
        "w_in": _normal(k[4], (d, 2 * ff), d ** -0.5),
        "w_out": _normal(k[5], (ff, d), ff ** -0.5),
    }


def embed(key, m: dict):
    return _normal(jax.random.fold_in(key, 1),
                   (m["vocab_size"], m["hidden_size"]), EMBED_STD)


def lm_head(key, m: dict):
    """(d, vocab); the tied models use embed(...).T instead."""
    d = m["hidden_size"]
    return _normal(jax.random.fold_in(key, 2), (d, m["vocab_size"]),
                   d ** -0.5)


def final_norm(key, m: dict):
    return 1.0 + _normal(jax.random.fold_in(key, 3), (m["hidden_size"],),
                         NORM_GAIN_STD)
