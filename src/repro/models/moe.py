"""Mixture-of-Experts FFN (Mixtral-style: top-2 of 8, softmax-renormalized;
DeepSeek-style: sigmoid scores with a correction bias, a shared expert).

Three dispatch implementations:
  * "dense"    — every token through every expert, gate-weighted sum.
                 O(E) overcompute; kept as the correctness oracle.
  * "dropping" — static-shape capacity dispatch (MaxText/MegaBlocks style):
                 argsort tokens by expert, keep the first C per expert
                 (C = T*k*cf/E), grouped expert GEMMs, weighted scatter-add
                 back. Compiles to fixed shapes; dropped tokens contribute 0
                 (residual passes them through).
  * "dropless" — `dropless_moe`, the serving path (every paged "moe"
                 block runs it): every (token, expert) pair that falls on
                 an expert this chip holds is computed, whatever the
                 batch; rows sorted by expert through one grouped GEMM.

Expert weights carry a leading E dim; the quantized path vmaps `qlinear`
over experts (per-expert scales — the granularity the paper prescribes for
per-channel weight quantization).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant import qlinear
from repro.kernels import ops
from repro.models.layers import Taps, init_mlp, mlp


def _maybe_constrain(x, sharding):
    """with_sharding_constraint when every named dim divides; the MoE
    dispatch scatter buffers otherwise replicate per device under GSPMD
    (200+ GiB/device at mixtral-8x22b train_4k)."""
    if sharding is None:
        return x
    spec = sharding.spec
    mesh = sharding.mesh
    if len(spec) > x.ndim:
        return x
    for dim, ax in zip(x.shape, spec):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if dim % n:
            return x
    return jax.lax.with_sharding_constraint(x, sharding)


def init_moe(key, cfg) -> dict:
    """Router over all `num_experts`; expert weights for the held ones
    only (`MoEConfig.held`); the shared expert, if any, as a dense MLP."""
    d, ff, m = cfg.d_model, cfg.d_ff, cfg.moe
    e = m.num_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    n_in = 2 * ff if cfg.act == "swiglu" else ff
    scale = 1.0 / jnp.sqrt(d)
    p = {
        "router": {"w": jax.random.normal(k1, (d, e), jnp.float32) * 0.02},
        "w_in": {"w": jax.random.normal(k2, (m.n_held, d, n_in),
                                        jnp.float32) * scale},
        "w_out": {"w": jax.random.normal(k3, (m.n_held, ff, d), jnp.float32)
                  / jnp.sqrt(ff)},
    }
    if m.scoring == "sigmoid":
        p["router"]["bias"] = jnp.zeros((e,), jnp.float32)
    if m.n_shared:
        p["shared"] = init_mlp(k4, d, m.n_shared * ff, cfg.act)
    return p


def _expert_ffn(p_in, p_out, x, act, qcfg, impl, constraint=None):
    """x: (E, C, d) through per-expert FFN -> ((E, C, d), hidden absmax (E, ff)).

    The hidden absmax is the calibration tap for w_out (recorded outside the
    vmap to keep the Taps accumulator trace-safe). `constraint` is the 2-D
    (tokens, features) dispatch sharding — it must be re-asserted on the
    expert *hidden* states or GSPMD all-gathers them to the full ff width
    (160 GiB/device at mixtral-8x22b train_4k).."""
    def one(pi, po, xe):
        h = qlinear.apply(pi, xe, qcfg, impl)
        h = _maybe_constrain(h, constraint)
        if act == "swiglu":
            g, u = jnp.split(h, 2, axis=-1)
            h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u
        elif act == "squared_relu":
            h = jnp.square(jax.nn.relu(h))
        else:
            h = jax.nn.gelu(h)
        h = _maybe_constrain(h, constraint)
        ham = jnp.max(jnp.abs(h.astype(jnp.float32)), axis=0)
        out = qlinear.apply(po, h, qcfg, impl)
        return _maybe_constrain(out, constraint), ham
    return jax.vmap(one)(p_in, p_out, x)


def _router(p, x, m):
    """x: (T, d) -> gates (T, k) f32, ids (T, k) int32, aux load-balance loss."""
    logits = (x.astype(jnp.float32) @ p["router"]["w"])        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # GShard aux loss: E * sum_e mean(prob_e) * mean(assign_e)
    e = probs.shape[-1]
    assign = jnp.zeros_like(probs).at[
        jnp.arange(ids.shape[0])[:, None], ids].set(1.0)
    aux = e * jnp.sum(jnp.mean(probs, 0) * jnp.mean(assign, 0))
    return gates, ids, aux


def moe_ffn(p: dict, x: jax.Array, cfg, qcfg=None, impl=None,
            taps: Optional[Taps] = None, tap_prefix: str = "",
            constraint=None):
    """x: (B, S, d) -> (out (B, S, d), aux_loss). `constraint`: optional
    NamedSharding with a (tokens, features) spec for dispatch buffers."""
    m = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    if taps is not None:
        taps.record(tap_prefix + "mlp_in", xt)
    if m.impl == "dropless":
        out, _ = dropless_moe(p, x, cfg, qcfg, impl, taps=taps,
                              tap_prefix=tap_prefix)
        return out, 0.0
    gates, ids, aux = _router(p, xt, m)
    if m.impl == "dense":
        out = _dense_moe(p, xt, gates, ids, cfg, qcfg, impl, taps, tap_prefix)
    else:
        out = _dropping_moe(p, xt, gates, ids, cfg, qcfg, impl, taps,
                            tap_prefix, constraint)
    return out.reshape(b, s, d).astype(x.dtype), aux


def _expand_expert(constraint):
    """(tokens, feat) constraint -> (E, capacity, feat) for the expert buf."""
    if constraint is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = constraint.spec
    return NamedSharding(constraint.mesh, P(None, spec[0], spec[1]))


def _expand_vec(constraint):
    """(tokens, feat) constraint -> (tokens,) for the dispatch index vectors."""
    if constraint is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(constraint.mesh, P(constraint.spec[0]))


def _dense_moe(p, xt, gates, ids, cfg, qcfg, impl, taps, tap_prefix):
    m = cfg.moe
    t = xt.shape[0]
    # (E, T, d): every expert sees every token (oracle; smoke-test sizes only)
    xe = jnp.broadcast_to(xt[None], (m.num_experts,) + xt.shape)
    he, ham = _expert_ffn(p["w_in"], p["w_out"], xe, cfg.act, qcfg, impl)
    if taps is not None:
        taps.record_absmax(tap_prefix + "mlp_out", ham)
    weight = jnp.zeros((t, m.num_experts), jnp.float32).at[
        jnp.arange(t)[:, None], ids].add(gates)
    return jnp.einsum("etd,te->td", he.astype(jnp.float32), weight)


def _dropping_moe(p, xt, gates, ids, cfg, qcfg, impl, taps, tap_prefix,
                  constraint=None):
    m = cfg.moe
    t, d = xt.shape
    e, k = m.num_experts, m.top_k
    cap = int(t * k * m.capacity_factor / e + 0.999)
    cap = max(8, min(t, -(-cap // 8) * 8))             # round up to 8, <= T

    flat_e = ids.reshape(-1)                           # (T*k,)
    flat_gate = gates.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), k)

    order = jnp.argsort(flat_e, stable=True)
    se, sg, st = flat_e[order], flat_gate[order], flat_tok[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(t * k) - starts[se]
    keep = rank < cap
    slot = jnp.where(keep, se * cap + rank, e * cap)   # overflow row dropped

    # Shard the (T*k,) dispatch vectors over the token axis: gathers indexed
    # by replicated index vectors replicate their (T*k, d) outputs.
    c1 = _expand_vec(constraint)
    se, sg, st = (_maybe_constrain(a, c1) for a in (se, sg, st))
    slot = _maybe_constrain(slot, c1)
    keep = _maybe_constrain(keep, c1)

    buf = jnp.zeros((e * cap + 1, d), xt.dtype).at[slot].set(
        _maybe_constrain(xt[st], constraint))
    ebuf = _maybe_constrain(buf[:-1].reshape(e, cap, d),
                            _expand_expert(constraint))
    he, ham = _expert_ffn(p["w_in"], p["w_out"], ebuf, cfg.act, qcfg, impl,
                          constraint)
    if taps is not None:
        taps.record_absmax(tap_prefix + "mlp_out", ham)
    he = _maybe_constrain(he.reshape(e * cap, d), constraint)
    contrib = he[jnp.minimum(slot, e * cap - 1)] * (sg * keep)[:, None]
    contrib = _maybe_constrain(contrib, constraint)
    out = jnp.zeros((t, d), jnp.float32).at[st].add(contrib.astype(jnp.float32))
    return _maybe_constrain(out, constraint)


# ---------------------------------------------------------------------------
# Dropless serving path: held experts, sorted rows, one grouped GEMM
# ---------------------------------------------------------------------------

def route(p: dict, xt: jax.Array, m):
    """Router over all experts, in float32. xt (T, d) -> (gates (T, k) f32,
    ids (T, k) int32).

    softmax: gates are the top-k probabilities, renormalised.
    sigmoid: s = sigmoid(x W_r); the top k of s + bias (the per-expert
    correction bias steers the choice only); gates are the chosen s,
    renormalised. Either way times `routed_scaling`."""
    logits = jnp.dot(xt.astype(jnp.float32), p["router"]["w"],
                     precision=jax.lax.Precision.HIGHEST)          # (T, E)
    if m.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + p["router"]["bias"], m.top_k)
        gates = jnp.take_along_axis(scores, ids, axis=-1)
    else:
        gates, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m.top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * m.routed_scaling, ids


def _int8_grouped(p: dict, qcfg) -> bool:
    """Whether `grouped_linear` can run the grouped int8 kernel: W8A8
    weights with nothing applied to the activations first."""
    if "w_q" not in p or qcfg is None:
        return False
    return (p["w_q"].bits == 8 and qcfg.act_bits == 8
            and p.get("smooth") is None and not qcfg.hadamard)


def grouped_linear(p: dict, x: jax.Array, sizes: jax.Array, qcfg=None,
                   impl=None, x_q=None):
    """Rows x (M, K) sorted by expert, expert e taking sizes[e] consecutive
    rows, each through its own expert's linear of the stack p ((E, K, N)
    weights). W8A8 runs `ops.grouped_int8_matmul` (x_q: the rows' int8
    codes and scales, when already quantized); any other form computes
    every expert over every row and keeps each row's own expert's result.
    Returns (M, N) bfloat16; rows past the groups are undefined."""
    if _int8_grouped(p, qcfg):
        if x_q is None:
            x_q = ops.quantize_act_dynamic(x, impl=impl)
        q, s = x_q
        wq = p["w_q"]
        return ops.grouped_int8_matmul(q, wq.data, s, wq.scale, sizes,
                                       out_dtype=jnp.bfloat16, impl=impl)
    owner = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(x.shape[0]),
                             side="right")
    n_exp = jax.tree.leaves(p)[0].shape[0]
    out = None
    for e in range(n_exp):
        y = qlinear.apply(jax.tree.map(lambda a: a[e], p), x, qcfg, impl)
        out = y if out is None else jnp.where((owner == e)[:, None], y, out)
    return out.astype(jnp.bfloat16)


def dropless_moe(p: dict, x: jax.Array, cfg, qcfg=None, impl=None,
                 valid: Optional[jax.Array] = None,
                 taps: Optional[Taps] = None, tap_prefix: str = ""):
    """The expert layer of a chip that holds experts [lo, hi) of the
    `num_experts` the router chooses among (`MoEConfig.held`; all of them
    by default), plus the shared experts:

        y = sum_{e in held, e chosen} w_e * SwiGLU_e(h)  +  SwiGLU_s(h)

    with w_e the router's gates (`route`). Dropless: every (token, expert)
    pair that falls on a held expert is computed, so a token's output does
    not depend on the rest of its batch; tokens that `valid` (x's leading
    shape) marks False (padding rows of a step) are routed nowhere. The
    pairs, sorted by held expert, run as rows of one grouped GEMM per
    linear, T * min(top_k, E_held) rows for T tokens; pairs routed to
    experts this chip does not hold are counted and left out.

    x (..., d) -> (y (..., d), {"rows": (E_held,) rows each held expert
    took, "absent": pairs routed to experts not held})."""
    m = cfg.moe
    d = x.shape[-1]
    k = m.top_k
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    if taps is not None:
        taps.record(tap_prefix + "mlp_in", xt)
    lo, hi = m.held_range
    n_held = hi - lo
    with jax.named_scope("router"):
        gates, ids = route(p, xt, m)
        ok = jnp.ones((t, 1), bool) if valid is None \
            else valid.reshape(t, 1)
        held = (ids >= lo) & (ids < hi) & ok
        absent = jnp.sum((~((ids >= lo) & (ids < hi))) & ok)
        local = jnp.where(held, ids - lo, n_held).reshape(-1)   # (T*k,)
        order = jnp.argsort(local, stable=True)
        sizes = jnp.bincount(local, length=n_held + 1)[:n_held]
        n_rows = t * min(k, n_held)        # held pairs a batch can hold
        tok = order[:n_rows] // k
        slot = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
    with jax.named_scope("experts"):
        xq = None
        if _int8_grouped(p["w_in"], qcfg):
            q, sc = ops.quantize_act_dynamic(xt, impl=impl)
            xq = (q[tok], sc[tok])
        h = grouped_linear(p["w_in"], xt[tok], sizes, qcfg, impl, xq)
        if cfg.act == "swiglu":
            g, u = jnp.split(h, 2, axis=-1)
            h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u
        elif cfg.act == "squared_relu":
            h = jnp.square(jax.nn.relu(h))
        else:
            h = jax.nn.gelu(h)
        if taps is not None:
            live = jnp.arange(n_rows) < jnp.sum(sizes)
            taps.record(tap_prefix + "mlp_out",
                        jnp.where(live[:, None], h, 0))
        y = grouped_linear(p["w_out"], h, sizes, qcfg, impl)   # (M, d)
        pick = jnp.minimum(slot, n_rows - 1)
        contrib = jnp.where(held.reshape(-1, 1), y[pick].astype(jnp.float32),
                            0.0)
        out = jnp.sum(contrib.reshape(t, k, d)
                      * gates.reshape(t, k, 1), axis=1)
    if m.n_shared:
        with jax.named_scope("shared_expert"):
            out = out + mlp(p["shared"], xt, cfg.act, qcfg, impl, taps,
                            tap_prefix + "shared_").astype(jnp.float32)
    stats = {"rows": sizes.astype(jnp.int32),
             "absent": absent.astype(jnp.int32)}
    return out.reshape(x.shape).astype(x.dtype), stats
