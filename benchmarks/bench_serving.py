"""Continuous-batching serving benchmark: decode tokens/sec, batched
prefill tokens/sec, TTFT, compile counts, and KV bytes/token for the fp16
vs int8 vs packed-int4 paged cache on the pangu_1b config.

    PYTHONPATH=src python benchmarks/bench_serving.py [--full] [--smoke]

Reports (and asserts, so the bench doubles as an acceptance gate):
  * int8 paged cache uses <= 55% of the fp16 pool's KV bytes/token
    (per-page per-head scales amortize the scale overhead to 4/page_size
    bytes per head; a per-token-scale layout would sit at ~56% for hd=32);
  * packed-int4 pages (two nibbles per byte along head_dim) use <= 30% of
    the fp16 pool's KV bytes/token, and the int4 engine is functional
    end-to-end: chunked prefill + prefix caching + speculative decode on
    packed pages emit valid tokens on at most 3 steady-state programs,
    warm prefix hits replay bit-identical packed codes + scales, and
    speculative truncate is bit-identical to a direct write;
  * chunked batched prefill (the mixed-step path, fused quantize-on-write)
    delivers >= 1.5x the prefill tokens/sec of the legacy per-admission
    path at batch 8, without regressing steady-state decode-step latency
    by more than 10%;
  * compile counts stay bounded: the chunked engine runs on exactly two
    steady-state programs (mixed + decode, zero one-shot prefills); the
    legacy engine compiles at most one prefill program per distinct
    power-of-two page bucket;
  * continuous batching at batch 8 delivers >= 2x the tokens/sec of the
    same engine run with a single slot (skipped under --smoke);
  * the Pallas paged kernels (interpret mode — this host has no TPU)
    produce the same tokens as the XLA gather path;
  * refcounted prefix caching: on a batch-8 workload sharing a 6-page
    system prompt, a warm cache cuts mean TTFT >= 2x vs the cold first
    batch (hit rate >= 0.5 on re-submission) without regressing the
    decode-step latency floor by more than 5% vs a cache-off engine;
  * self-speculative decoding (n-gram drafting + batched verify): >= 1.3x
    decode tok/s over the non-speculative engine at batch 8 on a
    repetitive (n-gram-friendly) workload with bit-exact greedy outputs
    on bf16 pools, <= 5% decode tok/s regression on an adversarial
    (low-acceptance) workload with int8 pools, and at most 3 steady-state
    programs (mixed + decode + verify; still zero one-shot prefills).

The speculative workloads are fixed (seed, prompt-index) picks into
make_prompts under this file's reduced pangu_1b config and PRNGKey(0)
weights: the friendly set is the 8 lanes whose greedy bf16 continuations
loop earliest (most drafter-predictable), the adversarial set 8 lanes
whose continuations never repeat an n-gram. The 1.3x/bit-exact gate runs
on bf16 pools because int8 page scales are recomputed from full-page
content on every write — a vanilla decode re-rounds the page token by
token, so each position sees a slightly different effective cache than
one shared-K verify pass can reproduce; int8 friendly numbers are
reported (acceptance rate, speedup) but only the regression bound is
gated there.

--json PATH dumps every reported metric as a JSON document (CI uploads it
as an artifact so runs are comparable across commits) — including decode
tok/s, TTFT percentiles, and speculative acceptance rates.

Throughput is measured on the jitted XLA paged path: interpret-mode Pallas
re-traces the kernel grid in Python and measures the interpreter, not the
serving engine. On a real Atlas-A2-class part the streaming kernels replace
the gathers; their correctness is what's gated here.

--smoke runs the gates (bytes ratios, prefill speedup, decode latency,
compile counts, kernel parity, int4 functional) on CI-sized shapes and
skips the batch sweep; scripts/ci.sh runs it on every push. --kv-bits
selects the pool dtype the engine-level legs (kernel parity, chunked vs
legacy prefill, prefix caching) run under — the CI int4 leg passes
`--kv-bits 4` so the whole serving path is exercised on packed pages and
its metrics land in a separate artifact.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

if importlib.util.find_spec("repro") is None:       # script run w/o PYTHONPATH
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_arch, reduced            # noqa: E402
from repro.data import DataConfig, make_prompts        # noqa: E402
from repro.launch.jax_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer                   # noqa: E402
from repro.serving import ContinuousBatchingEngine     # noqa: E402
from repro.serving import kv_pool                      # noqa: E402

PAGE = 16
CHUNK_PAGES = 2

# speculative workloads: (make_prompts seed, prompt index) under
# DataConfig(vocab=cfg.vocab, seq_len=64), 8 prompts of 24 tokens per seed
# — see the module docstring for how these lanes were picked and why the
# bit-exact gate runs on bf16 pools
SPEC_FRIENDLY = [(23, 2), (18, 2), (17, 1), (3, 2),
                 (27, 2), (21, 6), (16, 2), (25, 7)]
SPEC_ADVERSARIAL = [(12, 0), (17, 4), (32, 3), (29, 0),
                    (8, 4), (31, 7), (3, 5), (12, 1)]
SPEC_PROMPT_LEN = 24
SPEC_MAX_NEW = 256
SPEC_SEQ_LEN = 320
SPEC_K = 8
# one page of a fixed token plus a ramp: loops immediately, so one warmup
# run compiles the verify program alongside mixed + decode
SPEC_WARM_PROMPT = [7] * 8 + list(range(16))


def make_engine(params, cfg, *, kv_bits, max_batch, max_seq_len,
                paged_impl="auto", prefill_mode="chunked",
                prefix_cache=False):
    # full token budget: every slot advances a chunk per mixed step — the
    # batched-prefill configuration the >= 1.5x gate measures
    return ContinuousBatchingEngine(
        params, cfg, kv_bits=kv_bits, page_size=PAGE, max_batch=max_batch,
        max_seq_len=max_seq_len, paged_impl=paged_impl,
        prefill_mode=prefill_mode, chunk_pages=CHUNK_PAGES,
        token_budget=max_batch * CHUNK_PAGES * PAGE,
        prefix_cache=prefix_cache)


def throughput(eng, prompts, max_new):
    eng.run(prompts[:1], max_new=4)            # warm the jit caches
    t0 = time.time()
    res = eng.run(prompts, max_new=max_new)
    dt = time.time() - t0
    toks = sum(len(t) for t in res.tokens)
    return toks / dt, res


def prefill_metrics(eng, prompts, max_new=8):
    """Drive one batch through the engine, splitting the wall clock into a
    prefill phase (submit -> every request has its first token) and a
    steady decode phase. Returns prefill tok/s, TTFT, decode-step latency."""
    rids = [eng.submit(p, max_new=max_new) for p in prompts]
    pending = set(rids)
    ttft = {}
    t0 = time.time()
    while pending:
        eng.step()
        now = time.time()
        done = {r for r in pending if eng._requests[r].out}
        for r in done:
            ttft[r] = now - t0
        pending -= done
    prefill_s = time.time() - t0
    n_prompt = sum(len(eng._requests[r].prompt) for r in rids)
    dts = []
    while not eng.sched.idle:
        s0 = time.time()
        eng.step()
        dts.append(time.time() - s0)
    return {"prefill_tok_s": n_prompt / prefill_s,
            "ttft_mean_ms": 1e3 * float(np.mean(list(ttft.values()))),
            "ttft_max_ms": 1e3 * float(np.max(list(ttft.values()))),
            "ttft_all_ms": [1e3 * t for t in ttft.values()],
            "decode_dts": dts}


def best_prefill(eng, prompts, reps=3, max_new=8):
    """Best-of-reps to shave scheduler noise off CI boxes; decode-step
    samples pool across reps and report the 10th-percentile floor (medians
    of ~30 samples at ~1 ms/step swing +-50% run to run; the floor is what
    a latency regression would move)."""
    eng.run(prompts[:1], max_new=2)            # warm every program
    runs = [prefill_metrics(eng, prompts, max_new=max_new)
            for _ in range(reps)]
    dts = [d for r in runs for d in r["decode_dts"]]
    ttfts = [t for r in runs for t in r["ttft_all_ms"]]
    return {"prefill_tok_s": max(r["prefill_tok_s"] for r in runs),
            "ttft_mean_ms": min(r["ttft_mean_ms"] for r in runs),
            "ttft_max_ms": min(r["ttft_max_ms"] for r in runs),
            "ttft_percentiles_ms": {
                f"p{q}": float(np.percentile(ttfts, q))
                for q in (50, 90, 99)},
            "decode_ms": (1e3 * float(np.percentile(dts, 10)) if dts
                          else float("nan"))}


def decode_floor(eng, prompts, max_new, reps=3):
    """Steady-state decode-step latency floor in ms: finish every prefill,
    then time each pure-decode step and take the min across reps. The min
    is the stable estimator here — p10/median of ~1 ms host-loop steps
    swing +-10% run to run, far above the 5% regression this gates."""
    best = float("inf")
    for _ in range(reps):
        rids = [eng.submit(p, max_new=max_new) for p in prompts]
        while any(not eng._requests[r].out for r in rids):
            eng.step()
        dts = []
        while not eng.sched.idle:
            t0 = time.perf_counter()
            eng.step()
            dts.append(time.perf_counter() - t0)
        best = min(best, min(dts))
    return 1e3 * best


def spec_prompts(cfg, keys):
    """Materialize a fixed speculative workload: prompt `i` of the 8-prompt
    batch make_prompts generates under `seed`, for each (seed, i) key."""
    out = []
    for seed, i in keys:
        ps = make_prompts(DataConfig(vocab=cfg.vocab, seq_len=64, seed=seed),
                          8, SPEC_PROMPT_LEN)
        out.append(list(ps[i]))
    return out


def spec_engine(params, cfg, *, kv_bits, k):
    return ContinuousBatchingEngine(
        params, cfg, kv_bits=kv_bits, page_size=PAGE, max_batch=8,
        max_seq_len=SPEC_SEQ_LEN, prefill_mode="chunked",
        chunk_pages=CHUNK_PAGES, token_budget=8 * CHUNK_PAGES * PAGE,
        prefix_cache=True, spec_decode=k)


def decode_tok_s_pair(eng_a, eng_b, prompts, max_new=SPEC_MAX_NEW, reps=4):
    """Best-of-reps end-to-end decode throughput for two engines on the
    same workload (decode-dominated: 24-token prompts, 256 generated
    tokens/lane). Reps alternate engines so a drifting box slows both
    sides alike — two back-to-back solo measurements decorrelate and can
    swing a throughput *ratio* by more than the 5% the adversarial gate
    bounds."""
    out = []
    for eng in (eng_a, eng_b):
        eng.run([SPEC_WARM_PROMPT], max_new=32)   # compiles verify too
        out.append([0.0, None])
    for _ in range(reps):
        for eng, slot in zip((eng_a, eng_b), out):
            t0 = time.time()
            r = eng.run(prompts, max_new=max_new)
            dt = time.time() - t0
            tps = sum(len(t) for t in r.tokens) / dt
            if tps > slot[0]:
                slot[0], slot[1] = tps, r
    return out[0][0], out[0][1], out[1][0], out[1][1]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="pangu_1b")
    ap.add_argument("--full", action="store_true",
                    help="full-size config (default: reduced, CPU-sized)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: gates only, no batch sweep")
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--batches", type=int, nargs="*", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all reported metrics to PATH as JSON")
    ap.add_argument("--kv-bits", type=int, choices=[4, 8, 16], default=8,
                    help="pool dtype for the engine-level legs (kernel "
                    "parity, chunked-vs-legacy prefill, prefix caching); "
                    "the bytes and int4 gates always run")
    args = ap.parse_args(argv)
    enable_compile_cache()
    prompt_len = args.prompt_len or (48 if args.smoke else 16)
    max_new = args.max_new or (8 if args.smoke else 32)
    batches = args.batches or ([] if args.smoke else [1, 2, 4, 8, 16, 32])

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    max_seq_len = PAGE * -(-(prompt_len + max_new + 2) // PAGE)
    n_prompts = max(batches + [8])
    prompts = make_prompts(DataConfig(vocab=cfg.vocab, seq_len=64),
                           n_prompts, prompt_len)
    ok = True

    # -- KV bytes/token: fp16 vs int8 vs packed-int4 pool (geometry) --------
    bpt = {}
    for kv_bits in (16, 8, 4):
        eng = make_engine(params, cfg, kv_bits=kv_bits, max_batch=1,
                          max_seq_len=max_seq_len)
        bpt[kv_bits] = eng.kv_bytes_per_token()
    ratio = bpt[8] / bpt[16]
    ratio4 = bpt[4] / bpt[16]
    print(f"# KV bytes/token: fp16={bpt[16]:.1f} int8={bpt[8]:.1f} "
          f"int4={bpt[4]:.1f} (ratios {ratio:.3f} / {ratio4:.3f})")
    if ratio > 0.55:
        ok = False
        print(f"FAIL: int8 KV bytes/token ratio {ratio:.3f} > 0.55")
    if ratio4 > 0.30:
        ok = False
        print(f"FAIL: int4 KV bytes/token ratio {ratio4:.3f} > 0.30")

    # -- pallas kernels (interpret) vs XLA gather: same tokens --------------
    few = prompts[:2]
    r_xla = make_engine(params, cfg, kv_bits=args.kv_bits, max_batch=2,
                        max_seq_len=max_seq_len,
                        paged_impl="xla").run(few, max_new=8)
    r_pal = make_engine(params, cfg, kv_bits=args.kv_bits, max_batch=2,
                        max_seq_len=max_seq_len,
                        paged_impl="pallas_interpret").run(few, max_new=8)
    kernel_ok = r_xla.tokens == r_pal.tokens
    print(f"# pallas(interpret) == xla serving tokens: {kernel_ok}")
    if not kernel_ok:
        ok = False
        print("FAIL: pallas kernel tokens diverge from XLA path")

    # -- chunked vs legacy prefill at batch 8 -------------------------------
    b8 = prompts[:8]
    engines = {}
    for mode in ("chunked", "legacy"):
        engines[mode] = make_engine(params, cfg, kv_bits=args.kv_bits,
                                    max_batch=8, max_seq_len=max_seq_len,
                                    prefill_mode=mode)
    stats = {m: best_prefill(engines[m], b8, max_new=max_new)
             for m in engines}
    print(f"# {'mode':>8s} {'prefill tok/s':>13s} {'TTFT mean ms':>12s} "
          f"{'TTFT max ms':>11s} {'decode ms':>9s}")
    for m, s in stats.items():
        print(f"  {m:>8s} {s['prefill_tok_s']:13.1f} "
              f"{s['ttft_mean_ms']:12.1f} {s['ttft_max_ms']:11.1f} "
              f"{s['decode_ms']:9.2f}")
    speedup = stats["chunked"]["prefill_tok_s"] / \
        stats["legacy"]["prefill_tok_s"]
    lat = stats["chunked"]["decode_ms"] / stats["legacy"]["decode_ms"]
    print(f"# chunked vs legacy prefill speedup: {speedup:.2f}x "
          f"(decode-step latency ratio {lat:.2f})")
    if speedup < 1.5:
        ok = False
        print(f"FAIL: chunked prefill speedup {speedup:.2f}x < 1.5x")
    if not lat <= 1.10:
        ok = False
        print(f"FAIL: chunked decode-step latency ratio {lat:.2f} > 1.10")

    # -- compile counts -----------------------------------------------------
    cc_ch = engines["chunked"].compile_counts()
    cc_leg = engines["legacy"].compile_counts()
    print(f"# compile counts: chunked={cc_ch} legacy={cc_leg}")
    if cc_ch != {"prefill": 0, "mixed": 1, "decode": 1, "verify": 0}:
        ok = False
        print(f"FAIL: chunked engine is not two-program steady state: "
              f"{cc_ch}")
    # legacy buckets to powers of two: at most one program per distinct
    # pow2 page bucket across every prompt it prefilled
    need = {-(-(len(p) + 1) // PAGE) for p in b8} | {1}   # +directive; warmup
    buckets = {1 << (n - 1).bit_length() for n in need}
    if cc_leg["prefill"] > len(buckets):
        ok = False
        print(f"FAIL: legacy prefill compiled {cc_leg['prefill']} programs "
              f"> {len(buckets)} pow2 buckets")

    # -- prefix caching: shared 6-page system prompt at batch 8 -------------
    # warm-vs-cold TTFT on one cache-on engine: the jit warmup uses an
    # unrelated prompt so the first shared-prefix batch really runs cold,
    # then re-submissions hit the pages the first batch promoted.
    rng = np.random.default_rng(7)
    common = rng.integers(0, cfg.vocab, size=6 * PAGE).tolist()
    shared = [common + rng.integers(0, cfg.vocab, size=PAGE).tolist()
              for _ in range(8)]
    px_new = max(max_new, 16)                  # enough decode-step samples
    px_seq = PAGE * -(-(len(shared[0]) + px_new + 2) // PAGE)
    eng_on = make_engine(params, cfg, kv_bits=args.kv_bits, max_batch=8,
                         max_seq_len=px_seq, prefix_cache=True)
    eng_on.run(prompts[:1], max_new=2)         # jit warm, cache stays cold
    cold = prefill_metrics(eng_on, shared, max_new=px_new)
    h0 = eng_on.sched.prefix_hit_tokens
    p0 = eng_on.sched.prefix_prompt_tokens
    warm_runs = [prefill_metrics(eng_on, shared, max_new=px_new)
                 for _ in range(3)]
    hit_rate = (eng_on.sched.prefix_hit_tokens - h0) / \
        (eng_on.sched.prefix_prompt_tokens - p0)
    warm_ttft = min(r["ttft_mean_ms"] for r in warm_runs)
    ttft_speedup = cold["ttft_mean_ms"] / warm_ttft
    eng_off = make_engine(params, cfg, kv_bits=args.kv_bits, max_batch=8,
                          max_seq_len=px_seq)
    eng_off.run(prompts[:1], max_new=2)
    off_floor = decode_floor(eng_off, shared, max_new=px_new)
    on_floor = decode_floor(eng_on, shared, max_new=px_new)
    px_lat = on_floor / off_floor
    print(f"# prefix cache: cold TTFT {cold['ttft_mean_ms']:.1f} ms, "
          f"warm TTFT {warm_ttft:.1f} ms ({ttft_speedup:.2f}x), "
          f"warm hit rate {hit_rate:.2f}, decode floor on/off "
          f"{on_floor:.2f}/{off_floor:.2f} ms (ratio {px_lat:.2f})")
    if ttft_speedup < 2.0:
        ok = False
        print(f"FAIL: warm-cache TTFT speedup {ttft_speedup:.2f}x < 2x")
    if hit_rate < 0.5:
        ok = False
        print(f"FAIL: warm hit rate {hit_rate:.2f} < 0.5")
    if not px_lat <= 1.05:
        ok = False
        print(f"FAIL: prefix-cache decode-step latency ratio "
              f"{px_lat:.2f} > 1.05")
    px_stats = eng_on.prefix_cache_stats()

    # -- packed-int4 pool: functional + bit-exactness gates -----------------
    # e2e: chunked prefill + prefix caching + speculative decode on packed
    # pages, still within the 3-program steady state
    eng4 = spec_engine(params, cfg, kv_bits=4, k=SPEC_K)
    friendly = spec_prompts(cfg, SPEC_FRIENDLY)
    r4 = eng4.run(friendly, max_new=32)
    int4_tokens_ok = (all(len(t) == 32 for t in r4.tokens) and
                      all(0 <= tok < cfg.vocab
                          for t in r4.tokens for tok in t))
    cc4 = eng4.compile_counts()
    int4_programs_ok = (cc4["prefill"] == 0 and sum(cc4.values()) <= 3)
    # warm prefix hits must map the exact packed codes + scales the cold
    # pass wrote — never requantize or rewrite a shared page
    eng4.run(shared, max_new=8)
    cached = sorted(eng4.sched.cache._by_hash.values())
    before = jax.device_get(eng4.pools)
    h4 = eng4.sched.prefix_hit_tokens
    eng4.run(shared, max_new=8)
    after = jax.device_get(eng4.pools)
    int4_replay_ok = bool(cached) and \
        eng4.sched.prefix_hit_tokens > h4 and all(
            np.array_equal(before[blk][leaf][:, cached],
                           after[blk][leaf][:, cached])
            for blk in before for leaf in ("k", "v", "k_s", "v_s"))
    # speculative rollback: truncate == direct write of the accepted
    # prefix, bit-exact on packed nibbles and scales (page-exact rollback)
    geom = SimpleNamespace(n_kv_heads=2, hd=4)
    pool4 = kv_pool.init_pool(geom, 8, 4, kv_bits=4)
    rng4 = np.random.default_rng(3)
    rows = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    hist = jnp.asarray(rng4.normal(size=(2, 5, 2, 4)), jnp.float32)
    start = jnp.asarray([3, 1], jnp.int32)
    pool4 = kv_pool.write_chunk(pool4, hist, hist, rows,
                                jnp.zeros(2, jnp.int32), start)
    kw = jnp.asarray(rng4.normal(size=(2, 5, 2, 4)), jnp.float32)
    vw = jnp.asarray(rng4.normal(size=(2, 5, 2, 4)), jnp.float32)
    n_keep = jnp.asarray([2, 4], jnp.int32)
    snap = {leaf: pool4[leaf][rows] for leaf in pool4}
    pfull = kv_pool.write_chunk(pool4, kw, vw, rows, start,
                                jnp.full(2, 5, jnp.int32))
    rolled = kv_pool.truncate(pfull, rows, snap, kw, vw, start, n_keep)
    direct = kv_pool.write_chunk(pool4, kw, vw, rows, start, n_keep)
    int4_trunc_ok = all(np.array_equal(np.asarray(rolled[leaf]),
                                       np.asarray(direct[leaf]))
                        for leaf in pool4)
    print(f"# int4 pool: e2e tokens {int4_tokens_ok}, programs {cc4} "
          f"(<=3 {int4_programs_ok}), prefix replay bit-exact "
          f"{int4_replay_ok}, truncate bit-exact {int4_trunc_ok}")
    for cond, msg in ((int4_tokens_ok, "int4 engine emitted invalid tokens"),
                      (int4_programs_ok,
                       f"int4 engine exceeds 3 steady-state programs: "
                       f"{cc4}"),
                      (int4_replay_ok,
                       "int4 warm prefix hits rewrote packed pages"),
                      (int4_trunc_ok,
                       "int4 truncate differs from direct write")):
        if not cond:
            ok = False
            print(f"FAIL: {msg}")

    # -- speculative decoding at batch 8 ------------------------------------
    adversarial = spec_prompts(cfg, SPEC_ADVERSARIAL)
    spec = {"k": SPEC_K, "decode_tok_s": {}, "acceptance_rate": {}}
    for kv_bits in (16, 8):
        tag = "bf16" if kv_bits == 16 else "int8"
        van = spec_engine(params, cfg, kv_bits=kv_bits, k=0)
        sp = spec_engine(params, cfg, kv_bits=kv_bits, k=SPEC_K)
        v_f, rv, s_f, rs = decode_tok_s_pair(van, sp, friendly)
        spec["decode_tok_s"][f"vanilla_{tag}"] = v_f
        spec["decode_tok_s"][f"spec_{tag}"] = s_f
        spec[f"friendly_speedup_{tag}"] = s_f / v_f
        spec["acceptance_rate"][tag] = sp.spec_stats()["acceptance_rate"]
        if kv_bits == 16:
            spec["bit_exact_greedy_bf16"] = all(
                list(a) == list(b) for a, b in zip(rv.tokens, rs.tokens))
        else:
            v_a, _, s_a, _ = decode_tok_s_pair(van, sp, adversarial)
            spec["decode_tok_s"]["vanilla_int8_adversarial"] = v_a
            spec["decode_tok_s"]["spec_int8_adversarial"] = s_a
            spec["adversarial_ratio_int8"] = s_a / v_a
            spec["compile_counts"] = sp.compile_counts()
    print(f"# speculative (k={SPEC_K}): friendly bf16 "
          f"{spec['decode_tok_s']['vanilla_bf16']:.0f} -> "
          f"{spec['decode_tok_s']['spec_bf16']:.0f} tok/s "
          f"({spec['friendly_speedup_bf16']:.2f}x, acc "
          f"{spec['acceptance_rate']['bf16']:.2f}, bit-exact "
          f"{spec['bit_exact_greedy_bf16']}); friendly int8 "
          f"{spec['friendly_speedup_int8']:.2f}x (acc "
          f"{spec['acceptance_rate']['int8']:.2f}); adversarial int8 "
          f"{spec['adversarial_ratio_int8']:.2f}x; compile "
          f"{spec['compile_counts']}")
    if spec["friendly_speedup_bf16"] < 1.3:
        ok = False
        print(f"FAIL: speculative friendly speedup "
              f"{spec['friendly_speedup_bf16']:.2f}x < 1.3x")
    if not spec["bit_exact_greedy_bf16"]:
        ok = False
        print("FAIL: speculative greedy tokens diverge from vanilla (bf16)")
    if spec["adversarial_ratio_int8"] < 0.95:
        ok = False
        print(f"FAIL: speculative adversarial ratio "
              f"{spec['adversarial_ratio_int8']:.2f}x < 0.95x")
    cc_spec = spec["compile_counts"]
    if cc_spec["prefill"] + cc_spec["mixed"] + cc_spec["decode"] + \
            cc_spec["verify"] > 3:
        ok = False
        print(f"FAIL: speculative engine exceeds 3 steady-state programs: "
              f"{cc_spec}")

    # -- throughput sweep ---------------------------------------------------
    tput = {}
    if batches:
        print(f"# {'batch':>5s} {'kv':>4s} {'tok/s':>8s} {'steps':>6s} "
              f"{'KV B/tok':>9s}")
        for kv_bits in (16, 8):
            for b in batches:
                eng = make_engine(params, cfg, kv_bits=kv_bits, max_batch=b,
                                  max_seq_len=max_seq_len)
                tps, res = throughput(eng, prompts[:max(b, 8)], max_new)
                tput[(kv_bits, b)] = tps
                print(f"  {b:5d} {kv_bits:4d} {tps:8.1f} "
                      f"{res.steps_run + res.mixed_steps:6d} "
                      f"{eng.kv_bytes_per_token():9.1f}")
    if (8, 8) in tput and (8, 1) in tput:
        sp = tput[(8, 8)] / tput[(8, 1)]
        print(f"# continuous batch=8 vs single-slot speedup (int8 KV): "
              f"{sp:.2f}x")
        if sp < 2.0:
            ok = False
            print(f"FAIL: batch-8 speedup {sp:.2f}x < 2x")
    elif batches:
        print("# speedup check skipped (--batches does not include 1 and 8)")

    print("PASS" if ok else "FAIL")
    if args.json:
        doc = {
            "config": {"arch": args.arch, "full": args.full,
                       "smoke": args.smoke, "page_size": PAGE,
                       "chunk_pages": CHUNK_PAGES, "kv_bits": args.kv_bits,
                       "prompt_len": prompt_len, "max_new": max_new},
            "kv_bytes_per_token": {str(k): v for k, v in bpt.items()},
            "kv_bytes_ratio": ratio,
            "kv_bytes_ratio_int4": ratio4,
            "int4": {"tokens_ok": int4_tokens_ok,
                     "compile_counts": cc4,
                     "programs_ok": int4_programs_ok,
                     "prefix_replay_bitexact": int4_replay_ok,
                     "truncate_bitexact": int4_trunc_ok},
            "kernel_parity": kernel_ok,
            "prefill": {m: {k: v for k, v in s.items() if k != "decode_dts"}
                        for m, s in stats.items()},
            "chunked_prefill_speedup": speedup,
            "chunked_decode_latency_ratio": lat,
            "compile_counts": {"chunked": cc_ch, "legacy": cc_leg},
            "prefix_cache": {
                "cold_ttft_mean_ms": cold["ttft_mean_ms"],
                "warm_ttft_mean_ms": warm_ttft,
                "ttft_speedup": ttft_speedup,
                "warm_hit_rate": hit_rate,
                "decode_floor_on_ms": on_floor,
                "decode_floor_off_ms": off_floor,
                "decode_latency_ratio": px_lat,
                "engine_stats": px_stats,
            },
            "speculative": spec,
            "throughput_tok_s": {f"kv{k}_b{b}": v
                                 for (k, b), v in tput.items()},
            "pass": ok,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# metrics written to {args.json}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
