"""Chip smoke test: serve pangu_1b at its full published width on one TPU
chip, through the engine `launch/serve.py` builds (`build_engine`: random
seeded weights -> PTQ -> ContinuousBatchingEngine) with chunked prefill,
paged decode, the prefix cache and the Pallas kernels on. Three settings
run one after another in this one process:

    bf16 weights, kv_bits 16 | W8A8 (int8), kv_bits 8 | W4A8, kv_bits 4

Each serves 8 seeded prompts of 257 tokens (a 128-token shared prefix, so
the second wave of 4 hits the prefix cache) for 32 new tokens each, and
checks that:
  * every request finished with its 32 tokens, and the second wave was
    served partly from the prefix cache;
  * the compiled mixed and decode steps call the paged-attention kernel
    and, with quantized weights, the GEMM kernels;
  * each paged kernel agrees with its jnp oracle on the chip at these
    shapes (PARITY_RTOL);
  * (bf16) the logits of the first mixed step, and of one decode step
    after it, agree between the kernel and the oracle read path
    (LOGIT_RTOL).
Compile seconds, peak device memory and compile counts are printed for
information; they are not speed metrics.

    python chip_smoke.py

The last line of stdout is {"ok": true, "device": {...}}. Without a TPU,
or when any check fails, it exits non-zero and prints no such line.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time
from functools import partial

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 0
SETTINGS = (("bf16", 16), ("int8", 8), ("w4a8", 4))   # (weights, kv_bits)
LANES, PAGE, CHUNK_PAGES = 8, 16, 2
PROMPT, SHARED, MAX_NEW, MAX_SEQ = 256, 128, 32, 320
# Kernel parity's own table and lanes' key counts: one key, one page, a
# compute block (256 keys), one page past it, the whole table, and ragged
# counts in each of the three blocks.
PARITY_COLS = 40
PARITY_KEYS = (1, 16, 256, 272, PARITY_COLS * PAGE, 520, 100, 383)
WAVE = 4               # requests per wave; wave 2 reuses wave 1's prefix
CALIB_BATCHES = 2
# Kernel vs oracle, unit-normal q/K/V: both outputs are rounded to bf16,
# whose spacing is 2^-7 of a value's power of two, and the kernel's f32 dots
# may take bf16 MXU passes, while the oracle runs at "highest" matmul
# precision. Allowed: two bf16 spacings,
# |kernel - oracle| <= PARITY_RTOL * max(|oracle|, 1).
PARITY_RTOL = 2.0 ** -6
# Logits of one step, kernel vs oracle read path through 20 bf16 layers,
# over the real vocab: max |logit difference| <= LOGIT_RTOL * max |oracle
# logit|. On a v5e, bf16 rounding moves them by 0.015 of the scale, a kv_len
# one short in every layer by 0.09, a neighbouring kv head by 1.3 or more.
LOGIT_RTOL = 0.05
VERIFY_NOTE = ("speculative verify has no Pallas kernel: it runs the jnp "
               "gather path on every backend (not exercised here)")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[smoke] FAIL: {what}")


class CompileMeter:
    """Backend compile seconds (loads from the persistent cache included)
    and persistent-cache hits/misses, read off JAX's monitoring events."""

    def __init__(self, jax):
        self.seconds, self.hits, self.misses = 0.0, 0, 0

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def make_prompts(vocab: int):
    """8 seeded prompts: one shared 128-token prefix + 128 own tokens (ids
    below the reserved directive range at the top of the vocab)."""
    rng = np.random.default_rng(SEED)
    shared = rng.integers(0, vocab - 8, SHARED).tolist()
    return [shared + rng.integers(0, vocab - 8, PROMPT - SHARED).tolist()
            for _ in range(2 * WAVE)]


def kernel_parity(cfg, kv_bits: int) -> None:
    """Each paged kernel vs its jnp oracle on the chip, at the serving
    widths: 8 lanes over a pool of this pool dtype, with a 40-column page
    table (three compute blocks of the kernels' walk: 16, 16 and 8 pages)
    and key counts on the blocks' edges (PARITY_KEYS)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import paged_attn, paged_prefill
    from repro.serving import kv_pool

    cols = PARITY_COLS
    t = cols * PAGE
    c = CHUNK_PAGES * PAGE
    ks = jax.random.split(jax.random.PRNGKey(SEED + kv_bits), 4)
    k = jax.random.normal(ks[0], (LANES, t, cfg.n_kv_heads, cfg.hd))
    v = jax.random.normal(ks[1], (LANES, t, cfg.n_kv_heads, cfg.hd))
    table = jnp.arange(1, 1 + LANES * cols, dtype=jnp.int32).reshape(
        LANES, cols)
    pool = kv_pool.init_pool(cfg, 1 + LANES * cols, PAGE, kv_bits)
    pool = jax.jit(kv_pool.write_prefill)(
        pool, k, v, table, jnp.full((LANES,), t, jnp.int32))
    leaves = (pool["k"], pool["v"], pool.get("k_s"), pool.get("v_s"), table)
    lens = jnp.asarray(PARITY_KEYS, jnp.int32)
    # chunk lanes (n_new = c) beside decode lanes (n_new = 1), same key counts
    n_new = jnp.minimum(lens, jnp.asarray([c, 1] * (LANES // 2), jnp.int32))
    q_start, kv_len = lens - n_new, lens
    q1 = jax.random.normal(ks[2], (LANES, cfg.n_heads, cfg.hd), jnp.bfloat16)
    qc = jax.random.normal(ks[3], (LANES, c, cfg.n_heads, cfg.hd),
                           jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        refs = {"paged_decode_attention": jax.jit(
                    paged_attn.paged_decode_attention_ref)(q1, *leaves, lens),
                "paged_prefill_attention": jax.jit(
                    paged_prefill.paged_prefill_attention_ref)(
                        qc, *leaves, q_start, kv_len)}
    got = {"paged_decode_attention": paged_attn.paged_decode_attention(
               q1, *leaves, lens),
           "paged_prefill_attention": paged_prefill.paged_prefill_attention(
               qc, *leaves, q_start, kv_len)}
    for name, want in refs.items():
        want = want.astype(jnp.float32)
        diff = jnp.abs(got[name].astype(jnp.float32) - want)
        err = float(jnp.max(diff / jnp.maximum(jnp.abs(want), 1.0)))
        log(f"  parity {name} (kv_bits {kv_bits}): max |kernel - oracle| = "
            f"{float(jnp.max(diff))!r}; max |kernel - oracle| / "
            f"max(|oracle|, 1) = {err!r} (limit {PARITY_RTOL})")
        check(err <= PARITY_RTOL, f"{name} kv_bits={kv_bits} differs from "
              f"its oracle by {err} > {PARITY_RTOL} (relative)")


def compare_logits(step: str, got, want, vocab: int) -> None:
    """Kernel-path vs oracle-path logits over the real vocab (the padded
    LM-head columns hold -1e9 on both sides and are left out)."""
    got = np.asarray(got)[..., :vocab]
    want = np.asarray(want)[..., :vocab]
    diff = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    agree = int((got.argmax(-1) == want.argmax(-1)).sum())
    log(f"  {step} logits, kernel vs oracle: max |diff| = {diff!r}, max "
        f"|oracle logit| = {scale!r}, ratio {diff / scale!r} (limit "
        f"{LOGIT_RTOL}); top-1 agrees on {agree}/{LANES} lanes")
    check(bool(np.isfinite(got).all()), f"non-finite {step} logits from "
          "the kernel path")
    check(diff <= LOGIT_RTOL * scale, f"{step} logits differ by {diff} > "
          f"{LOGIT_RTOL} x max |oracle logit| {scale}")


def step_logits(eng, cfg, prompts) -> None:
    """The first mixed step (every lane's first chunk), then one decode step
    from the oracle's pools, each through the engine's kernel program and
    through the oracle read path."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer
    from repro.serving.kv_pool import chunk_window_pages

    c = CHUNK_PAGES * PAGE
    wc = chunk_window_pages(c, PAGE)
    cols = MAX_SEQ // PAGE
    held = CHUNK_PAGES + 1             # the chunk's pages + the decode write
    table = np.zeros((LANES, cols), np.int32)
    table[:, :held] = np.arange(1, 1 + LANES * held).reshape(LANES, held)
    windows = np.zeros((LANES, wc), np.int32)
    windows[:, :CHUNK_PAGES] = table[:, :CHUNK_PAGES]
    toks = np.asarray([p[:c] for p in prompts], np.int32)
    zeros = np.zeros(LANES, np.int32)
    n_new = np.full(LANES, c, np.int32)
    rows = transformer.pack_rows(toks, zeros, n_new, LANES * c)
    table, *args = [jnp.asarray(a)
                    for a in (table, windows, *rows, zeros, n_new)]
    pools = transformer.init_paged_pools(cfg, eng.sched.alloc.n_pages, PAGE,
                                         16)

    # every lane's chunk is more rows than the engine's own sizes hold: the
    # engine's mixed program compiled at LANES x c rows, beside its own
    kernel = eng._mixed.lower(eng.params, pools, table, *args).compile()
    got = kernel(eng.params, jax.tree.map(jnp.copy, pools), table, *args)[0]
    mixed = jax.jit(partial(transformer.prefill_chunk_paged, cfg=cfg,
                            paged_impl="xla"))
    want, pools = mixed(eng.params, pools, table, *args)
    compare_logits("first mixed step", got, want, cfg.vocab)

    nxt = jnp.asarray(np.asarray(want)[:, :cfg.vocab].argmax(-1), jnp.int32)
    pos = jnp.full((LANES,), c, jnp.int32)
    decode = jax.jit(partial(transformer.decode_step_paged, cfg=cfg,
                             paged_impl="xla"))
    want, _ = decode(eng.params, pools, table, nxt, pos)
    got = eng._decode(eng.params, pools, table, nxt, pos)[0]
    compare_logits("next decode step", got, want, cfg.vocab)


def run_setting(cfg, quant: str, kv_bits: int, prompts, meter) -> None:
    import jax
    from repro.kernels import ops
    from repro.launch.serve import build_engine

    log(f"setting: weights={quant} kv_bits={kv_bits}")
    c0 = meter.snapshot()
    eng = build_engine(cfg, quant=quant, seed=SEED,
                       calib_batches=CALIB_BATCHES, kv_bits=kv_bits,
                       page_size=PAGE, max_batch=LANES, max_seq_len=MAX_SEQ,
                       chunk_pages=CHUNK_PAGES, prefix_cache=True)
    check(eng.paged_impl == "pallas", f"paged_impl resolved to "
          f"{eng.paged_impl!r}, not the kernel")

    kernel_parity(cfg, kv_bits)
    if kv_bits == 16:
        step_logits(eng, cfg, prompts)

    want = {"mixed": {"paged_prefill_attention"},
            "decode": {"paged_decode_attention"}}
    gemm = {"bf16": set(), "int8": {"quantize_act_dynamic", "int8_matmul"},
            "w4a8": {"quantize_act_dynamic", "w4a8_matmul"}}[quant]
    lowered = eng.lower_steps()
    for step, low in [("decode", lowered["decode"]),
                      *(("mixed", m) for m in lowered["mixed"].values())]:
        found = ops.compiled_kernels(low.compile().as_text())
        log(f"  compiled {step} step calls tpu_custom_call kernels: "
            f"{sorted(found)}")
        missing = (want[step] | gemm) - found
        check(not missing, f"{step} step lacks kernels {sorted(missing)}")

    t0 = time.time()
    first = eng.run(prompts[:WAVE], mode="slow_think", max_new=MAX_NEW)
    t1 = time.time()
    second = eng.run(prompts[WAVE:], mode="slow_think", max_new=MAX_NEW)
    t2 = time.time()
    tokens = first.tokens + second.tokens
    check(all(len(t) == MAX_NEW for t in tokens),
          f"requests finished with {[len(t) for t in tokens]} tokens, "
          f"want {MAX_NEW} each")
    check(all(0 <= x < cfg.vocab for t in tokens for x in t),
          "token ids outside the vocab")
    check(second.prefix_hit_tokens > 0, "wave 2 got no prefix-cache hits")
    log(f"  served {len(tokens)} requests x {MAX_NEW} tokens: wave 1 "
        f"{t1 - t0!r} s (first use of the steps), wave 2 {t2 - t1!r} s; "
        f"{first.mixed_steps + second.mixed_steps} mixed + "
        f"{first.steps_run + second.steps_run} decode steps; wave 2 "
        f"prompt tokens from the prefix cache: {second.prefix_hit_tokens}")
    log(f"  sample: {tokens[0][:8]}")
    counts = eng.compile_counts()
    log(f"  compile_counts: {counts}")
    check(counts["mixed"] == len(eng.row_sizes) and counts["decode"] == 1,
          f"steady state is not one mixed program per row size "
          f"{eng.row_sizes} + one decode program: {counts}")
    secs, hits, misses = (a - b for a, b in zip(meter.snapshot(), c0))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"  backend compile or cache load {secs!r} s; persistent cache "
        f"hits {hits}, "
        f"misses {misses}; peak device memory of the process so far "
        f"{'not reported' if peak is None else f'{peak} bytes'}")


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] no TPU found (JAX platform {dev.platform!r}); the "
              "chip smoke runs only on a TPU", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_arch
    from repro.launch.jax_cache import enable_compile_cache

    log(f"device: {device}")
    log(f"compile cache: {enable_compile_cache()}")
    meter = CompileMeter(jax)
    cfg = get_arch("pangu_1b")
    log(f"model: {cfg.name} at full width: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, hd {cfg.hd}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}; random weights, seed {SEED}")
    log(f"note: {VERIFY_NOTE}")
    prompts = make_prompts(cfg.vocab)
    for quant, kv_bits in SETTINGS:
        run_setting(cfg, quant, kv_bits, prompts, meter)
        gc.collect()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
