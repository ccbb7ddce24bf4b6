"""Seeded float32 layers of a dense GQA decoder (`bench/weights.py` makes
the embedding, the LM head and the final norm).

Sizes from the configuration's `model`: hidden_size, num_attention_heads,
num_key_value_heads, head_dim, intermediate_size.
"""
from __future__ import annotations

import jax

from bench.weights import NORM_GAIN_STD, normal


def layer(key, i, m: dict) -> dict:
    """Layer `i` (an int or a traced scalar): RMSNorm gains and the fused
    projections [q | k | v] (d, (nq + 2 nkv) hd), o (nq hd, d),
    [gate | up] (d, 2 ff) and down (ff, d), each N(0, 1/fan_in)."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    nq, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    k = jax.random.split(jax.random.fold_in(key, 1000 + i), 6)
    return {
        "ln1": 1.0 + normal(k[0], (d,), NORM_GAIN_STD),
        "wqkv": normal(k[1], (d, (nq + 2 * nkv) * hd), d ** -0.5),
        "wo": normal(k[2], (nq * hd, d), (nq * hd) ** -0.5),
        "ln2": 1.0 + normal(k[3], (d,), NORM_GAIN_STD),
        "w_in": normal(k[4], (d, 2 * ff), d ** -0.5),
        "w_out": normal(k[5], (ff, d), ff ** -0.5),
    }
