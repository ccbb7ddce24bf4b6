"""CPU tests of the architecture as a part of the harness found by name
(`bench/arch/<arch>/`, named by a configuration's "arch"): a configuration
without the key, or naming an architecture the root does not hold, stops
the run with an error that says which; and an architecture the harness has
never seen, written only into a temporary root beside its configuration,
traffic and BENCHMARK.json, runs through the program's paged serving path,
is judged correct, turns incorrect when a token is altered, and gives every
per-layer reader that all cells report a number or nothing.

That architecture differs from the dense one where a model of sparse
experts does: its layers are of two kinds (dense, then a mixture of
experts), a layer of experts has more than four quantized linears, and the
rows of each expert's linears depend on routing. It has its own seeded
weights, its own reference (softmax router, the top k renormalised,
per-expert W8A8) and its own counts, and the program serves it with its
"moe" block beside "self", with a capacity at which no token drops."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import adapter  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.drive import Record, StepRecord  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bw": 819e9}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
# the per-layer readers BENCHMARK.json gives no `workloads` list, so that
# every cell, of any architecture, reports them
EVERY_CELL = ("step_ms.decode", "mfu.decode_step",
              "paged_decode_attention_roofline", "idle_share")

ARCH = "toy_experts"
ARCH_PY = '''"""Layers alternate between dense GQA + SwiGLU ("self") and
GQA + a routed mixture of SwiGLU experts ("moe"): a float32 softmax router
over `num_experts`, the top `experts_per_token` renormalised, each expert
its own W8A8 linears; the program's "moe" block, at a capacity that drops
nothing."""
import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.reference import HI, attention, bf, linear, rms_norm, rope
from bench.weights import (NORM_GAIN_STD, base_key, embed, final_norm,
                           lm_head, normal)

KINDS = ("self", "moe")


def layer_weights(key, i, m, kind):
    d, ff, e = m["hidden_size"], m["intermediate_size"], m["num_experts"]
    nq, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    k = jax.random.split(jax.random.fold_in(key, 1000 + i), 7)
    w = {"ln1": 1.0 + normal(k[0], (d,), NORM_GAIN_STD),
         "wqkv": normal(k[1], (d, (nq + 2 * nkv) * hd), d ** -0.5),
         "wo": normal(k[2], (nq * hd, d), (nq * hd) ** -0.5),
         "ln2": 1.0 + normal(k[3], (d,), NORM_GAIN_STD)}
    experts = () if kind == "self" else (e,)
    w["w_in"] = normal(k[4], experts + (d, 2 * ff), d ** -0.5)
    w["w_out"] = normal(k[5], experts + (ff, d), ff ** -0.5)
    if kind == "moe":
        w["router"] = normal(k[6], (d, e), d ** -0.5)
    return w


# -- program mapping ----------------------------------------------------------

def arch_config(name, m):
    from repro.configs.base import ArchConfig, MoEConfig
    e, k = m["num_experts"], m["experts_per_token"]
    return ArchConfig(
        name=name, family="moe", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab=m["vocab_size"], pattern=KINDS,
        tie_embeddings=m["tie_word_embeddings"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], act="swiglu",
        # capacity e / k: room in every expert for every token
        moe=MoEConfig(num_experts=e, top_k=k, capacity_factor=e / k,
                      impl="dropping"))


def _program_layer(w, kind):
    tree = {"ln1": {"g": w["ln1"]}, "ln2": {"g": w["ln2"]},
            "attn": {"wqkv": {"w": w["wqkv"]}, "wo": {"w": w["wo"]}}}
    ffn = {"w_in": {"w": w["w_in"]}, "w_out": {"w": w["w_out"]}}
    if kind == "moe":
        tree["moe"] = dict(ffn, router={"w": w["router"]})
    else:
        tree["mlp"] = ffn
    return tree


def make_params(cfg, m, qcfg, seed):
    from repro.core.quant import ptq
    group_cfg = dataclasses.replace(cfg, n_layers=len(KINDS))

    def group(key, g):
        tree = {str(j): _program_layer(
                    layer_weights(key, len(KINDS) * g + j, m, kind), kind)
                for j, kind in enumerate(KINDS)}
        if qcfg is None:
            return tree
        return ptq.quantize_model({"blocks": tree}, group_cfg, qcfg)[
            "blocks"]

    @jax.jit
    def build(key):
        params = {"embed": {"w": embed(key, m)},
                  "blocks": jax.lax.map(lambda g: group(key, g),
                                        jnp.arange(cfg.n_groups)),
                  "final_norm": {"g": final_norm(key, m)}}
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": lm_head(key, m)}
        return params

    return build(base_key(seed))


# -- reference ----------------------------------------------------------------

def _swiglu(h, w_in, w_out, p):
    gate, up = jnp.split(bf(linear(h, w_in, p)), 2, axis=-1)
    return bf(linear(bf(bf(jax.nn.silu(gate)) * up), w_out, p))


def _layer(x, w, n_real, l0, m, p, page, kind):
    nq, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    t = x.shape[0]
    pos = jnp.arange(t)
    qkv = bf(linear(rms_norm(x, w["ln1"], eps), w["wqkv"], p))
    q = rope(qkv[:, : nq * hd].reshape(t, nq, hd), pos, theta)
    k = rope(qkv[:, nq * hd:(nq + nkv) * hd].reshape(t, nkv, hd), pos, theta)
    v = qkv[:, (nq + nkv) * hd:].reshape(t, nkv, hd)
    a = bf(attention(q, k, v, n_real, l0, page, p["kv_bits"]))
    x = bf(x + bf(linear(a, w["wo"], p)))
    h = rms_norm(x, w["ln2"], eps)
    if kind == "self":
        return bf(x + _swiglu(h, w["w_in"], w["w_out"], p))
    probs = jax.nn.softmax(jnp.dot(h, w["router"], precision=HI), -1)
    gates, ids = jax.lax.top_k(probs, m["experts_per_token"])
    gates = gates / gates.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(m["num_experts"]):
        share = jnp.sum(jnp.where(ids == e, gates, 0.0), -1)
        out = out + share[:, None] * _swiglu(h, w["w_in"][e],
                                             w["w_out"][e], p)
    return bf(x + bf(out))


@functools.lru_cache(maxsize=None)
def _programs(m_items, p_items, page):
    m, p = dict(m_items), dict(p_items)

    def program(kind):
        return jax.jit(lambda x, key, i, n_real, l0: _layer(
            x, layer_weights(key, i, m, kind), n_real, l0, m, p, page, kind))

    return {kind: program(kind) for kind in KINDS}


def forward(x, key, m, p, page, n_real, l0):
    progs = _programs(tuple(sorted(m.items())), tuple(sorted(p.items())),
                      page)
    for i in range(m["num_hidden_layers"]):
        x = progs[KINDS[i % len(KINDS)]](x, key, i, n_real, l0)
    return x


# -- counts -------------------------------------------------------------------

def attention_layers(m):
    return m["num_hidden_layers"]


def gemms(m, tokens):
    d, ff, e = m["hidden_size"], m["intermediate_size"], m["num_experts"]
    nq, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    attn = [(tokens, d, (nq + 2 * nkv) * hd), (tokens, nq * hd, d)]
    dense = attn + [(tokens, d, 2 * ff), (tokens, ff, d)]
    routed = tokens * m["experts_per_token"] / e   # rows of each expert
    moe = attn + [(routed, d, 2 * ff)] * e + [(routed, ff, d)] * e
    return (dense + moe) * (m["num_hidden_layers"] // len(KINDS))


def attention_ops_per_key(m):
    return 4 * m["num_attention_heads"] * m["head_dim"]


def query_bytes(m):
    return 2 * 2 * m["num_attention_heads"] * m["head_dim"]


def kv_bytes_per_token(m, kv_bits):
    return 2 * m["num_key_value_heads"] * m["head_dim"] * kv_bits / 8


def scale_bytes_per_page(m, kv_bits):
    return 0 if kv_bits == 16 else 2 * m["num_key_value_heads"] * 4
'''

MODEL = {"hidden_size": 128, "num_hidden_layers": 4,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "intermediate_size": 128, "num_experts": 4, "experts_per_token": 2,
         "vocab_size": 512, "tie_word_embeddings": False,
         "rope_theta": 10000.0, "rms_norm_eps": 1e-05}
SPEC = {
    "arch": ARCH,
    "model": MODEL,
    "quant": {"weights": "int8", "weight_bits": 8, "weight_group": 0,
              "act_bits": 8, "kv_bits": 8},
    "control": {"weight_bits": 4, "weight_group": 0, "act_bits": 4,
                "kv_bits": 4},
    "engine": {"page_size": 16, "max_batch": 4, "max_seq_len": 256,
               "n_pages": 65, "chunk_pages": 2, "token_budget": 128,
               "prefix_cache": True},
    "check": {"logit_gap_limit": 0.5},
}
MIX = {"loop": "open", "mode": "no_think", "rate_per_s": 6, "warm_s": 0.3,
       "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                         "min": 20, "max": 100},
       "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
       "schedule_seed": 1}


def _root(root, spec, arch_files=True):
    """A checkout-like directory: BENCHMARK.json naming one configuration
    and one mix, their files, the readers of the metrics it names (copied
    from the harness), and the architecture's package."""
    for sub in ("configs", "traffic", "metrics"):
        (root / "bench" / sub).mkdir(parents=True)
    if arch_files:
        (root / "bench" / "arch" / ARCH).mkdir(parents=True)
        (root / "bench" / "arch" / ARCH / "__init__.py").write_text(ARCH_PY)
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(spec))
    (root / "bench" / "traffic" / "toy-mix.json").write_text(json.dumps(MIX))
    for name in ("setup_s", "itl_p95_ms", "ttft_p50_ms") + EVERY_CELL:
        shutil.copy(os.path.join(ROOT, "bench", "metrics", f"{name}.py"),
                    root / "bench" / "metrics")
    bench = {
        "configs": [{"name": "toy", "source": "toy", "reduced": [],
                     "file": "bench/configs/toy.json", "why": "toy"}],
        "workloads": [{"name": "toy.mix", "config": "toy",
                       "traffic": "toy-mix", "chips": 1, "why": "toy"}],
        "end_to_end": [
            {"name": n, "unit": u, "better": "lower", "bound": 0.25,
             "source": "host_clock"}
            for n, u in (("ttft_p50_ms", "ms"), ("itl_p95_ms", "ms"),
                         ("setup_s", "s"))],
        "per_layer": [{"name": n, "unit": "x", "better": "lower",
                       "source": "host_clock", "layer": "toy",
                       "moves": "itl_p95_ms"} for n in EVERY_CELL],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def _run(root, trace=False, seed=5):
    old = bench_run.DRAIN_S
    bench_run.DRAIN_S = 5.0
    try:
        return bench_run.run_cell(root, "toy.mix", seed, 1.0, trace,
                                  device=DEVICE, peaks=PEAKS,
                                  persistent_cache=False)
    finally:
        bench_run.DRAIN_S = old


@pytest.fixture(scope="module")
def experts_root(tmp_path_factory):
    return _root(tmp_path_factory.mktemp("experts"), SPEC)


def test_an_architecture_in_new_files_only_is_correct(experts_root):
    res = _run(experts_root, trace=True)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["tokens_compared"]["value"] > 0
    assert res["checks"]["logit_gap"]["value"] <= 0.5
    # on the CPU the trace has no device ops: the device's readers give
    # nothing; the host's give a number
    got = res["metrics"]
    assert set(got) <= set(EVERY_CELL)
    assert {"step_ms.decode", "mfu.decode_step"} <= set(got)
    assert all(v["value"] > 0 for v in got.values())


class _AlteredTokens(adapter.ContinuousBatchingEngine):
    """The engine with every sampled token moved by one id, where the
    tokens are produced."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        sample = self._sample
        self._sample = lambda lg: (sample(lg) + 1) % (self.cfg.vocab - 8)


def test_an_altered_token_makes_it_incorrect(experts_root, monkeypatch):
    monkeypatch.setattr(adapter, "ContinuousBatchingEngine", _AlteredTokens)
    res = _run(experts_root)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["max"]


def test_its_layers_differ_from_the_dense_ones(experts_root):
    """Two kinds of layer; ten quantized linears in a layer of experts, the
    experts' rows a share of the tokens as routing spreads them."""
    arch = bench_run.load_arch(experts_root, SPEC)
    sites = arch.gemms(MODEL, 6)
    per_group = len(sites) // (MODEL["num_hidden_layers"] // 2)
    assert per_group == 4 + 2 + 2 * MODEL["num_experts"]
    experts = sites[6:per_group]
    assert all(r == 6 * 2 / 4 for r, _, _ in experts)
    # each of the 6 tokens through 2 experts' [gate|up] and down
    assert sum(r * k * n for r, k, n in experts) == \
        6 * 2 * (128 * 2 * 128 + 128 * 128)


@pytest.mark.parametrize("traced", [
    None,
    {"kernel_s": {}, "busy_s": 0.9, "window_s": 1.0},
    {"kernel_s": {"paged_decode_attention": 1e-3}, "busy_s": 0.5,
     "window_s": 1.0}])
def test_every_cells_readers_read_any_architecture(experts_root, traced):
    arch = bench_run.load_arch(experts_root, SPEC)
    steps = [StepRecord(1.0, 1.04, "decode", [(40, 1), (90, 1)], 2, True),
             StepRecord(2.0, 2.1, "mixed", [(0, 32), (7, 1)], 1, True)]
    ctx = bench_run.Context(Record((0.0, 10.0), 10.0, [], steps), MODEL,
                            dict(SPEC["quant"], page_size=16), SPEC["engine"],
                            PEAKS, 3.0, traced, arch)
    got = {n: bench_run.load_reader(ROOT, n)(ctx) for n in EVERY_CELL}
    assert all(v is None or v > 0 for v in got.values())
    assert got["step_ms.decode"] == pytest.approx(40.0)
    assert got["mfu.decode_step"] is not None
    assert (got["idle_share"] is not None) == (traced is not None)
    assert (got["paged_decode_attention_roofline"] is not None) == bool(
        traced and traced["kernel_s"])


def test_a_configuration_without_arch_is_refused(tmp_path):
    spec = {k: v for k, v in SPEC.items() if k != "arch"}
    root = _root(tmp_path, spec)
    with pytest.raises(ValueError, match=r"bench/configs/toy\.json names "
                       r"no architecture.*'toy_experts'"):
        _run(root)


@pytest.mark.parametrize("name", ["dense_gqa", "no_such_arch", "../arch"])
def test_an_architecture_the_root_does_not_hold_is_refused(tmp_path, name):
    root = _root(tmp_path, dict(SPEC, arch=name))
    with pytest.raises(ValueError, match=r"bench/configs/toy\.json names "
                       r"architecture .* which .* does not hold"):
        _run(root)


def test_a_root_without_architectures_is_refused(tmp_path):
    root = _root(tmp_path, SPEC, arch_files=False)
    with pytest.raises(ValueError, match=r"names architecture "
                       r"'toy_experts'.*holds \[\]"):
        bench_run.load_arch(root, SPEC, "bench/configs/toy.json")


def test_an_architecture_is_loaded_once_per_directory(experts_root):
    dense = bench_run.load_arch(ROOT, {"arch": "dense_gqa"})
    assert dense is bench_run.load_arch(ROOT, {"arch": "dense_gqa"})
    assert dense is not bench_run.load_arch(experts_root, SPEC)
