"""Reference layer equations of a dense GQA decoder (RMSNorm, RoPE, grouped
query attention over the paged store, SwiGLU) and its loop over layers; the
shared arithmetic is `bench/reference.py`'s."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import attention, bf, linear, rms_norm, rope

from . import weights


def layer(x, w, n_real, l0, m, p, page):
    """One decoder layer over the whole sequence x (T, d)."""
    nq, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    t = x.shape[0]
    pos = jnp.arange(t)
    h = rms_norm(x, w["ln1"], eps)
    qkv = bf(linear(h, w["wqkv"], p))
    q = qkv[:, : nq * hd].reshape(t, nq, hd)
    k = qkv[:, nq * hd:(nq + nkv) * hd].reshape(t, nkv, hd)
    v = qkv[:, (nq + nkv) * hd:].reshape(t, nkv, hd)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    a = bf(attention(q, k, v, n_real, l0, page, p["kv_bits"]))
    x = bf(x + bf(linear(a, w["wo"], p)))
    h = rms_norm(x, w["ln2"], eps)
    gu = bf(linear(h, w["w_in"], p))
    gate, up = jnp.split(gu, 2, axis=-1)
    act = bf(bf(jax.nn.silu(gate)) * up)
    return bf(x + bf(linear(act, w["w_out"], p)))


@functools.lru_cache(maxsize=None)
def _layer_program(m_items, p_items, page):
    m, p = dict(m_items), dict(p_items)

    @jax.jit
    def one_layer(x, key, i, n_real, l0):
        return layer(x, weights.layer(key, i, m), n_real, l0, m, p, page)

    return one_layer


def forward(x, key, m: dict, p: dict, page: int, n_real: int, l0: int):
    """The embedded sequence x (T, d) through every layer, one jitted layer
    at a time, its weights made again from `key`."""
    one_layer = _layer_program(tuple(sorted(m.items())),
                               tuple(sorted(p.items())), page)
    for i in range(m["num_hidden_layers"]):
        x = one_layer(x, key, i, n_real, l0)
    return x
