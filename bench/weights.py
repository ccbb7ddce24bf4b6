"""Seeded weights, made on the device from the seed: the parts every
architecture shares (the PRNG key, the token embedding, the LM head and the
final norm's gains). An architecture's own layers come from its package,
`bench/arch/<arch>/`, through the same `normal`.

One generator serves both sides of the comparison that decides `correct`:
the architecture hands its float32 output to the program's post-training
quantization, and the plain reference makes the same float32 weights again,
one layer at a time, after the program's state is freed. Nothing here
imports the program.

Sizes come from a configuration file's `model` (`bench/configs/<name>.json`):
hidden_size, vocab_size.
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


def normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def embed(key, m: dict):
    return normal(jax.random.fold_in(key, 1),
                  (m["vocab_size"], m["hidden_size"]), EMBED_STD)


def lm_head(key, m: dict):
    """(d, vocab); the tied models use embed(...).T instead."""
    d = m["hidden_size"]
    return normal(jax.random.fold_in(key, 2), (d, m["vocab_size"]),
                  d ** -0.5)


def final_norm(key, m: dict):
    return 1.0 + normal(jax.random.fold_in(key, 3), (m["hidden_size"],),
                        NORM_GAIN_STD)
