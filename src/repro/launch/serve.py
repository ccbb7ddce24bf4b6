"""Serving launcher: load (or train) a model, PTQ it, serve batched
requests across the three CoT reasoning modes through the continuous-
batching engine over the paged (optionally int8 or int4) KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch pangu-1b --reduced \
        --quant int8 --kv-bits 8 --requests 8 --max-new 24

`--mode all` (the default) serves every request under each CoT mode in
turn and prints the study (mean length, repetition rate); a single
`--mode` serves them once under that mode.
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Optional

import jax

from repro.checkpoint import Checkpointer
from repro.configs import get_arch, reduced
from repro.core.quant import calibrate, preset, ptq
from repro.data import DataConfig, SyntheticLM, make_prompts
from repro.kernels import ops
from repro.launch.jax_cache import enable_compile_cache
from repro.models import transformer
from repro.serving import ContinuousBatchingEngine


def _validate(ap: argparse.ArgumentParser, args) -> None:
    """Fail fast on invalid flag combinations with a CLI-level error
    (argparse usage + exit 2) instead of tripping an assert or ValueError
    deep inside the engine after model init and PTQ."""
    if args.spec_decode < 0:
        ap.error("--spec-decode must be >= 0")
    if args.chunk_pages < 1:
        ap.error("--chunk-pages must be >= 1")
    if args.chunk_pages * args.page_size > args.max_seq_len:
        ap.error(f"--chunk-pages {args.chunk_pages} x --page-size "
                 f"{args.page_size} exceeds --max-seq-len "
                 f"{args.max_seq_len}")
    # the engine appends one CoT directive token to each prompt
    if args.prompt_len + 1 + args.max_new > args.max_seq_len:
        ap.error(f"--prompt-len {args.prompt_len} + 1 directive token + "
                 f"--max-new {args.max_new} exceeds --max-seq-len "
                 f"{args.max_seq_len}; raise --max-seq-len")
    if args.sampler == "temperature":
        if args.temperature <= 0:
            ap.error("--temperature must be > 0")
        if not 0 < args.top_p <= 1:
            ap.error("--top-p must be in (0, 1]")


def build_engine(cfg, *, quant: str = "fp16", seed: int = 0,
                 ckpt_dir: Optional[str] = None, calib_batches: int = 4,
                 calib_len: int = 32, **engine_kw):
    """Init (or restore) weights, post-training-quantize them under the
    `quant` preset (calibrated on `calib_batches` synthetic batches of
    `calib_len` tokens), and build the ContinuousBatchingEngine (`seed`
    also seeds its sampler key; engine_kw go to its constructor) — the
    one path from weights to a serving engine, shared by `main` and
    chip_smoke.py. GEMM and paged-attention kernels resolve "auto":
    Pallas on TPU, XLA elsewhere."""
    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    if ckpt_dir:
        params = Checkpointer(ckpt_dir).restore(params)
        print(f"[serve] restored params from {ckpt_dir}")
    qcfg = preset(quant)
    if qcfg is not None:
        dcfg = DataConfig(vocab=cfg.vocab, seq_len=calib_len, seed=seed + 1)
        t0 = time.time()
        stats = calibrate.collect_stats(
            params, SyntheticLM(dcfg).batches(0, calib_batches, 4), cfg)
        params = ptq.quantize_model(params, cfg, qcfg, stats)
        print(f"[serve] PTQ ({quant}) done in {time.time() - t0:.1f}s; "
              f"calibrated on {calib_batches} batches")
    return ContinuousBatchingEngine(params, cfg, qcfg=qcfg, seed=seed,
                                    **engine_kw)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default="int8",
                    choices=["fp16", "int8", "w4a8", "w4a8-smooth",
                             "w4a8-smooth-auto", "w4a8-hadamard"])
    ap.add_argument("--kv-bits", type=int, default=16, choices=[4, 8, 16])
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq-len", type=int, default=256)
    ap.add_argument("--paged-impl", default="auto", choices=list(ops.IMPLS),
                    help="paged-attention read path (auto: the Pallas "
                    "kernel on TPU, the jnp oracle elsewhere)")
    ap.add_argument("--chunk-pages", type=int, default=2,
                    help="prefill chunk size in pages")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-step token budget across prefill chunks and "
                    "decode lanes (default: one chunk + all decode lanes)")
    ap.add_argument("--prefix-cache", default="on", choices=["on", "off"],
                    help="share quantized prompt pages across requests via "
                    "refcounted page-table entries")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="draft-free speculative decoding: propose up to K "
                    "tokens per sequence per step via n-gram prompt lookup "
                    "and verify them in one batched step (0 disables)")
    ap.add_argument("--sampler", default="greedy",
                    choices=["greedy", "temperature"],
                    help="token sampler; temperature uses rejection-"
                    "sampling acceptance under --spec-decode")
    ap.add_argument("--temperature", type=float, default=0.8,
                    help="softmax temperature for --sampler temperature")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus filter for --sampler temperature "
                    "(1.0 disables)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore trained weights (else random init)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--mode", default="all",
                    choices=["all", "slow_think", "auto_think", "no_think"])
    ap.add_argument("--calib-batches", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    _validate(ap, args)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if not transformer.supports_paged(cfg):
        ap.error(f"--arch {args.arch} cannot be served: the paged engine "
                 f"needs full attention over token inputs (pattern "
                 f"{cfg.pattern}, sliding_window {cfg.sliding_window}, "
                 f"frontend {cfg.frontend!r})")

    enable_compile_cache()
    eng = build_engine(
        cfg, quant=args.quant, seed=args.seed, ckpt_dir=args.ckpt_dir,
        calib_batches=args.calib_batches,
        calib_len=max(32, args.prompt_len), kv_bits=args.kv_bits,
        page_size=args.page_size, max_batch=args.max_batch,
        max_seq_len=args.max_seq_len, paged_impl=args.paged_impl,
        chunk_pages=args.chunk_pages, token_budget=args.token_budget,
        prefix_cache=args.prefix_cache == "on",
        spec_decode=args.spec_decode, sampler=args.sampler,
        temperature=args.temperature, top_p=args.top_p)
    prompts = make_prompts(DataConfig(vocab=cfg.vocab, seq_len=64),
                           args.requests, args.prompt_len)
    t0 = time.time()
    if args.mode == "all":
        study = eng.cot_study(prompts, max_new=args.max_new)
        outs = [g for r in study.values() for g in r["generations"]]
        for mode, r in study.items():
            print(f"[serve] mode={mode:11s} mean_len={r['mean_len']:.1f} "
                  f"repetition_rate={r['repetition_rate']:.2f}")
            print(f"        sample: {r['generations'][0][:16]}")
    else:
        outs = eng.run(prompts, mode=args.mode, max_new=args.max_new).tokens
        for i, toks in enumerate(outs[:4]):
            print(f"[serve] req {i}: {len(toks)} tokens: {toks[:16]}")
    dt = time.time() - t0
    total = sum(len(t) for t in outs)
    print(f"[serve] {len(outs)} requests, {total} tokens in {dt:.1f}s "
          f"({total / dt:.1f} tok/s), {eng.mixed_steps} mixed + "
          f"{eng.steps_run} decode steps, {eng.prefill_tokens} prompt "
          f"tokens chunked, {eng.sched.n_evictions} evictions, "
          f"KV {eng.kv_bytes_per_token():.0f} B/token")
    tel = eng.telemetry.summary()
    if tel["ttft_p50_ms"] is not None:
        print(f"[serve] TTFT p50 {tel['ttft_p50_ms']:.1f} ms "
              f"(admission wait p50 {tel['admit_wait_p50_ms']:.1f} ms, "
              f"prefill p50 {tel['prefill_p50_ms']:.1f} ms); host "
              f"{tel['host_ms_per_step']:.2f} ms per step outside the "
              f"device wait, over {tel['steps']} steps")
    if args.spec_decode:
        st = eng.spec_stats()
        print(f"[serve] speculative: {st['spec_steps']} verify steps, "
              f"acceptance {st['acceptance_rate']:.2f} "
              f"({st['accepted_tokens']}/{st['draft_tokens']} proposals)")
    if eng.prefix_cache:
        st = eng.prefix_cache_stats()
        print(f"[serve] prefix cache: hit rate {st['hit_rate']:.2f} "
              f"({st['hit_tokens']}/{st['prompt_tokens']} prompt tokens), "
              f"{st['cached_pages']} cached pages "
              f"({st['unreferenced_pages']} unreferenced)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
