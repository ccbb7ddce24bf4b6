"""Token samplers for the serving engine (greedy / temperature / top-p)
and rejection-sampling acceptance for speculative decoding.

`speculative_accept` scores a verified draft window: position 0 holds the
last committed token, positions 1..n_new-1 hold drafter proposals, and
`logits[:, i]` is the model's distribution *after* window position i. The
drafter is deterministic (a point mass), so rejection sampling degenerates
to: accept draft d_{i+1} with probability p_i(d_{i+1}); on the first
rejection resample from the residual max(p - q, 0)/Z, which for a point
mass is p with the rejected token zeroed out, renormalized. Greedy is the
zero-temperature limit: accept iff d_{i+1} == argmax p_i, emit argmax —
token-for-token what a vanilla greedy decode loop would produce.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e9


def greedy(logits, key=None):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def temperature(logits, key, temp: float = 0.8):
    return jax.random.categorical(key, logits / temp, axis=-1).astype(jnp.int32)


def filter_top_p(logits, p: float = 0.9):
    """Nucleus filter: keep the smallest set of top tokens whose probability
    mass reaches p (ties at the threshold are all kept); the rest drop to
    NEG_INF. p >= 1 is the identity."""
    if p >= 1.0:
        return logits
    probs = jax.nn.softmax(logits, axis=-1)
    sp = jnp.flip(jnp.sort(probs, axis=-1), axis=-1)
    mass_before = jnp.cumsum(sp, axis=-1) - sp
    keep = mass_before < p          # token enters before the mass reaches p
    thr = jnp.min(jnp.where(keep, sp, 2.0), axis=-1, keepdims=True)
    return jnp.where(probs >= thr, logits, NEG_INF)


def top_p(logits, key, p: float = 0.9, temp: float = 0.8):
    return jax.random.categorical(
        key, filter_top_p(logits / temp, p), axis=-1).astype(jnp.int32)


def speculative_accept(logits, draft, n_new, key, *, mode: str = "greedy",
                       temp: float = 1.0, top_p: float = 1.0):
    """Accept/reject a verified draft window per sequence.

    logits: (B, C, V) f32 — model distribution after each window position;
    draft: (B, C) int32 — column 0 is the last committed token, columns
    1..n_new-1 are drafter proposals (the rest is padding);
    n_new: (B,) valid window tokens (0 = idle lane, 1 = no draft);
    key: PRNG key (unused for mode="greedy").

    Returns (emit (B, C) int32, acc (B,) int32): acc counts the leading
    accepted draft tokens (0 <= acc <= n_new-1); emit[:, j] is the token
    the engine emits at window step j — emit[:, :acc] echoes the accepted
    drafts, emit[:, acc] is the bonus/resample token, and columns past acc
    are garbage the caller must ignore.
    """
    b, c, _ = logits.shape
    i = jnp.arange(c - 1)[None, :]
    in_window = i + 1 < n_new[:, None]                       # draft i+1 valid
    if mode == "greedy":
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # (B, C)
        ok = (g[:, :-1] == draft[:, 1:]) & in_window
        acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        return g, acc

    lg = filter_top_p(logits / temp, top_p)
    probs = jax.nn.softmax(lg, axis=-1)                      # (B, C, V)
    k_u, k_full, k_res = jax.random.split(key, 3)
    # deterministic (point-mass) proposal: accept d with probability p(d)
    p_d = jnp.take_along_axis(probs[:, :-1], draft[:, 1:, None], -1)[..., 0]
    u = jax.random.uniform(k_u, (b, c - 1))
    ok = (u < p_d) & in_window
    acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
    # bonus token (all drafts accepted): sample the full distribution;
    # rejection at j: sample the residual — p with the rejected draft
    # token removed, renormalized (guard: an empty residual falls back to
    # the full distribution, which can only happen when p(d) ~ 1 so the
    # rejection branch itself has vanishing probability)
    full = jax.random.categorical(k_full, lg, axis=-1).astype(jnp.int32)
    d_next = jnp.roll(draft, -1, axis=1)                     # draft after j
    res_lg = jnp.where(jax.nn.one_hot(d_next, lg.shape[-1], dtype=bool),
                       NEG_INF, lg)
    res_lg = jnp.where(
        jnp.max(res_lg, axis=-1, keepdims=True) <= NEG_INF, lg, res_lg)
    resid = jax.random.categorical(k_res, res_lg, axis=-1).astype(jnp.int32)
    all_accepted = acc[:, None] >= jnp.maximum(n_new - 1, 0)[:, None]
    at_acc = jnp.where(all_accepted, full, resid)
    j = jnp.arange(c)[None, :]
    emit = jnp.where(j < acc[:, None], d_next, at_acc)
    return emit, acc
