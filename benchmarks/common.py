"""Shared benchmark fixtures: a tiny *trained* openPangu-class model (PTQ on
converged weights, not random init), calibration stats, quantized variants,
and the synthetic-task accuracy metric.

"Task accuracy" for the synthetic Markov stream = fraction of generated
tokens that are valid successors of their predecessor under the generating
chain — a real correctness criterion for generations (the HumanEval
pass-rate analog; see DESIGN.md §7)."""
from __future__ import annotations

import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.configs import get_arch, reduced
from repro.core.quant import calibrate, preset, ptq
from repro.data import DataConfig, SyntheticLM, make_prompts
from repro.models import transformer
from repro.optim import adamw
from repro.serving import ContinuousBatchingEngine
from repro.train import trainer

BENCH_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "bench")
BENCH_DIR = os.path.abspath(BENCH_DIR)
TRAIN_STEPS = 300
SEQ = 64
BATCH = 16


def _data(cfg, seed=0):
    return SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=SEQ, seed=seed))


_CACHE = {}


def trained_model(arch: str = "pangu_1b"):
    """Train (or restore) the tiny benchmark subject. Returns
    (cfg, params, data, stats)."""
    if arch in _CACHE:
        return _CACHE[arch]
    cfg = reduced(get_arch(arch), groups=2)
    data = _data(cfg)
    ck = Checkpointer(os.path.join(BENCH_DIR, f"model_{arch}"))
    ocfg = adamw.OptConfig(lr=3e-3, warmup_steps=30, total_steps=TRAIN_STEPS)
    state = trainer.init_state(jax.random.PRNGKey(0), cfg, ocfg)
    if ck.latest_step() == TRAIN_STEPS:
        state = ck.restore(state)
    else:
        step = jax.jit(trainer.make_train_step(cfg, ocfg, remat=False))
        t0 = time.time()
        for i in range(TRAIN_STEPS):
            state, m = step(state, data.batch(i, BATCH))
        print(f"# trained {arch} for {TRAIN_STEPS} steps in "
              f"{time.time() - t0:.0f}s; loss={float(m['loss']):.3f}")
        ck.save(TRAIN_STEPS, state, blocking=True)
    params = state.params
    stats = calibrate.collect_stats(
        params, data.batches(10_000, 8, BATCH), cfg)
    out = (cfg, params, data, stats)
    _CACHE[arch] = out
    return out


def outlier_model(arch: str = "pangu_1b", scale: float = 32.0):
    """The trained model pushed into the activation-outlier regime real LLMs
    exhibit (SmoothQuant reports ~100x channels): a fixed 1/8 of embedding
    channels scaled up, stats recalibrated. Fig 1 / Table 2's mechanism
    claims are evaluated here; the clean tiny model has no outliers."""
    key = ("outlier", arch)
    if key in _CACHE:
        return _CACHE[key]
    cfg, params, data, _ = trained_model(arch)
    import numpy as np
    emb = np.array(params["embed"]["w"], copy=True)
    rng = np.random.default_rng(11)
    idx = rng.choice(cfg.d_model, size=cfg.d_model // 8, replace=False)
    emb[:, idx] *= scale
    params = dict(params)
    params["embed"] = {"w": jnp.asarray(emb)}
    stats = calibrate.collect_stats(params, data.batches(10_000, 8, BATCH),
                                    cfg)
    out = (cfg, params, data, stats)
    _CACHE[key] = out
    return out


def undertrained_model(arch: str = "pangu_1b", steps: int = 60):
    """A weaker subject (the paper's 1B-vs-7B robustness contrast analog)."""
    key = ("under", arch, steps)
    if key in _CACHE:
        return _CACHE[key]
    cfg = reduced(get_arch(arch), groups=2)
    data = _data(cfg)
    ocfg = adamw.OptConfig(lr=3e-3, warmup_steps=10, total_steps=steps)
    state = trainer.init_state(jax.random.PRNGKey(0), cfg, ocfg)
    step = jax.jit(trainer.make_train_step(cfg, ocfg, remat=False))
    for i in range(steps):
        state, _ = step(state, data.batch(i, BATCH))
    stats = calibrate.collect_stats(state.params,
                                    data.batches(10_000, 4, BATCH), cfg)
    out = (cfg, state.params, data, stats)
    _CACHE[key] = out
    return out


def quantized_variants(cfg, params, stats, names=("int8", "w4a8",
                                                  "w4a8-smooth",
                                                  "w4a8-hadamard")):
    out = {"fp16": (None, params)}
    for name in names:
        qcfg = preset(name)
        out[name] = (qcfg, ptq.quantize_model(params, cfg, qcfg, stats))
    return out


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def eval_logits(params, cfg, data, qcfg=None, n_batches=4, start=20_000):
    outs = []
    for i in range(n_batches):
        b = data.batch(start + i, BATCH)
        logits, _ = transformer.forward_train(
            params, b, cfg, qcfg=qcfg, impl="xla" if qcfg else None,
            remat=False)
        outs.append((logits, b["labels"]))
    return outs


def perplexity(pairs):
    tot, n = 0.0, 0
    for logits, labels in pairs:
        logp = jax.nn.log_softmax(logits, -1)
        nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        tot += float(jnp.sum(nll))
        n += labels.size
    return float(np.exp(tot / n))


def agreement_and_kl(pairs_ref, pairs_q):
    agree, kl, n = 0.0, 0.0, 0
    for (lr, _), (lq, _) in zip(pairs_ref, pairs_q):
        agree += float(jnp.sum(jnp.argmax(lr, -1) == jnp.argmax(lq, -1)))
        p = jax.nn.softmax(lr, -1)
        kl += float(jnp.sum(p * (jax.nn.log_softmax(lr, -1)
                                 - jax.nn.log_softmax(lq, -1))))
        n += lr.shape[0] * lr.shape[1]
    return agree / n, kl / n


def successor_accuracy(data, prompts, generations):
    """Fraction of generated tokens that are valid Markov successors."""
    succ = np.asarray(data.succ)
    total, ok = 0, 0
    for p, g in zip(prompts, generations):
        seq = list(p) + list(g)
        for a, b in zip(seq[len(p) - 1:-1], seq[len(p):]):
            if a < succ.shape[0]:
                ok += int(b in succ[a])
                total += 1
    return ok / max(total, 1)


def engines_for(cfg, variants):
    return {name: ContinuousBatchingEngine(p, cfg, qcfg=q,
                                           impl="xla" if q else None)
            for name, (q, p) in variants.items()}


def bench_prompts(cfg, n=16, prompt_len=12):
    return make_prompts(DataConfig(vocab=cfg.vocab, seq_len=SEQ), n,
                        prompt_len)


def row(name: str, us_per_call: float, derived) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
