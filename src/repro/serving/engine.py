"""Serving engines: the legacy padded-batch engine and the continuous-
batching engine over the paged, optionally int8-quantized KV pool.

`ServingEngine` (legacy): requests are packed left-aligned into fixed batch
slots with a per-request `lengths` vector against dense per-slot caches;
the whole batch enters and leaves together.

`ContinuousBatchingEngine` (tentpole): a PagedScheduler admits/evicts
requests *each step* into fixed batch slots; KV lives in fixed-size pages
(serving/kv_pool.py) handed out from a free list, so memory scales with
tokens actually held rather than slots x max_len, and finished sequences'
pages are immediately reusable. The three CoT think modes are just
different (directive token, stop policy) pairs feeding the same scheduler
(cot.StopPolicy).

Prefill admission comes in two modes:

  * "chunked" (default, Sarathi/vLLM-style): prompts stream through the
    scheduler in fixed-shape page-aligned chunks of `chunk_pages` pages.
    Each step batches prompt chunks from up to `token_budget` worth of
    prefilling slots *together with* every ongoing decode slot into one
    jitted mixed step (`transformer.prefill_chunk_paged`) whose K/V is
    quantized directly into int8 pages (`kv_pool.write_chunk`) — no dense
    bf16 cache and no second `_to_pages` pass. Steady state compiles
    exactly two programs: the mixed step (any prefill in flight) and the
    pure decode step.
  * "legacy" (per-admission prefill, kept for A/B): each admitted request
    runs a one-shot dense prefill at a power-of-two page bucket, then its
    cache is scattered into pages. One extra compilation per distinct
    bucket; decode stalls while prefill runs.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models import transformer
from repro.serving import cot, sampling, telemetry
from repro.serving.draft import NgramDrafter
from repro.serving.kv_pool import (SCRATCH_PAGE, chunk_window_pages,
                                   verify_window_pages)
from repro.serving.scheduler import PagedScheduler, Request


@dataclasses.dataclass
class GenerationResult:
    tokens: List[List[int]]          # generated tokens per request
    modes: List[str]
    prompt_lens: List[int]
    steps_run: int


class ServingEngine:
    def __init__(self, params, cfg, *, qcfg=None, impl=None, kv_bits=16,
                 eos_id: Optional[int] = None, dtype=jnp.bfloat16):
        self.params = params
        self.cfg = cfg
        self.qcfg = qcfg
        self.impl = impl
        self.kv_bits = kv_bits
        self.eos_id = eos_id
        self.dtype = dtype
        self._prefill = jax.jit(
            partial(transformer.prefill, cfg=cfg, qcfg=qcfg, impl=impl,
                    kv_bits=kv_bits, dtype=dtype),
            static_argnames=("max_len",))
        self._decode = jax.jit(
            partial(transformer.decode_step, cfg=cfg, qcfg=qcfg, impl=impl,
                    dtype=dtype))

    # -- request packing ------------------------------------------------------

    def _pack(self, prompts: Sequence[Sequence[int]]):
        b = len(prompts)
        lens = np.array([len(p) for p in prompts], np.int32)
        s = int(lens.max())
        toks = np.zeros((b, s), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        return jnp.asarray(toks), jnp.asarray(lens)

    # -- generation -----------------------------------------------------------

    def generate(self, prompts: Sequence[Sequence[int]], *, max_new: int = 32,
                 mode: str = "slow_think", sampler: str = "greedy",
                 seed: int = 0, ctx=None) -> GenerationResult:
        """Generate under a CoT mode. Directive token appended per paper §4.1;
        per-request budgets follow the mode policy (auto_think adapts)."""
        cfg = self.cfg
        prompts = [cot.apply_mode(p, mode, cfg.vocab) for p in prompts]
        budgets = np.array([cot.budget_for(mode, len(p), max_new)
                            for p in prompts], np.int32)
        toks, lens = self._pack(prompts)
        b, s = toks.shape
        max_len = s + int(budgets.max()) + 1

        batch = {"tokens": toks, "lengths": lens}
        if ctx is not None:
            batch["ctx"] = ctx
        logits, caches = self._prefill(self.params, batch, max_len=max_len)

        sample = sampling.SAMPLERS[sampler]
        key = jax.random.PRNGKey(seed)
        pos = lens                       # next position to write per request
        cur = (sample(logits) if sampler == "greedy"
               else sample(logits, key))
        out = [[] for _ in range(b)]
        active = np.ones(b, bool)
        steps = 0
        for step in range(int(budgets.max())):
            cur_np = np.asarray(cur)
            for i in range(b):
                if active[i]:
                    out[i].append(int(cur_np[i]))
                    if self.eos_id is not None and cur_np[i] == self.eos_id:
                        active[i] = False
                    if len(out[i]) >= budgets[i]:
                        active[i] = False
            if not active.any():
                break
            logits, caches = self._decode(self.params, caches, cur, pos)
            key, sub = jax.random.split(key)
            cur = (sample(logits) if sampler == "greedy"
                   else sample(logits, sub))
            pos = pos + 1
            steps += 1
        return GenerationResult(tokens=out, modes=[mode] * b,
                                prompt_lens=[len(p) for p in prompts],
                                steps_run=steps)

    # -- paper-style analysis -------------------------------------------------

    def cot_study(self, prompts, *, max_new=32, sampler="greedy", seed=0):
        """Run all three CoT modes; return per-mode generations + stats
        (Figure 2 lengths / Figure 4 repetition inputs)."""
        results = {}
        for mode in cot.MODES:
            r = self.generate(prompts, max_new=max_new, mode=mode,
                              sampler=sampler, seed=seed)
            results[mode] = {
                "generations": r.tokens,
                "mean_len": float(np.mean([len(t) for t in r.tokens])),
                "repetition_rate": cot.repetition_rate(r.tokens),
            }
        return results


# ---------------------------------------------------------------------------
# Continuous batching over the paged KV pool
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ContinuousResult:
    tokens: List[List[int]]          # generated tokens, submission order
    modes: List[str]
    prompt_lens: List[int]
    steps_run: int                   # pure batched decode steps
    decode_tokens: int               # tokens produced by decode lanes
    evictions: int
    mixed_steps: int = 0             # chunked prefill+decode steps
    prefill_tokens: int = 0          # prompt tokens written via chunks
    prefix_hit_tokens: int = 0       # prompt tokens served from the cache
    spec_steps: int = 0              # speculative verify steps
    draft_tokens: int = 0            # drafter proposals scored
    accepted_tokens: int = 0         # proposals accepted (excl. bonus)


class ContinuousBatchingEngine:
    """Continuous-batching inference over a paged, optionally int8 KV cache.

    max_batch slots x ceil(max_seq_len / page_size) page-table columns; the
    pool defaults to full occupancy (every slot can reach max_seq_len) —
    pass a smaller n_pages to exercise preemption. Greedy sampling (the
    deterministic serving path the paper's CoT study measures).

    prefix_cache=True (chunked mode only) shares quantized prompt pages
    across requests via the page table: admission maps the longest cached
    prefix of full prompt pages in bit-exact (no recompute), and only the
    uncached tail is chunk-prefilled; finished requests promote their
    prompt pages. Cache hits change page-table *contents*, never step
    shapes, so compile_counts() stays at the two-program steady state.

    spec_decode=k (chunked mode only) turns on draft-free self-speculative
    decoding: in pure-decode steps an n-gram prompt-lookup drafter
    (serving/draft.py) proposes up to k tokens per lane and one jitted
    verify program (fixed k+1 window — at most one extra compilation)
    scores them all, committing accepted prefixes through the fused
    quantize-on-write path and rolling back rejected suffixes page-exactly
    (kv_pool.truncate + scheduler.truncate_to). With sampler="greedy" and
    bf16 pools the emitted tokens are bit-exact with vanilla greedy decode
    (quantized int8/int4 pools score the draft window's K/V
    pre-quantization, a deviation within quantization noise);
    sampler="temperature" accepts via
    rejection sampling (sampling.speculative_accept), preserving the
    target distribution. A cost-model gate bounds the overhead on
    n-gram-free workloads: a verify step only runs when the drafted total
    times a running acceptance estimate clears spec_gate extra tokens per
    lane (the measured verify/decode cost ratio), and consecutive thin
    drafting backs the host-side lookup off exponentially (doubling
    cooldown capped at spec_cooldown decode steps).
    """

    def __init__(self, params, cfg, *, qcfg=None, impl=None, kv_bits=16,
                 page_size: int = 16, max_batch: int = 8,
                 max_seq_len: int = 256, n_pages: Optional[int] = None,
                 eos_id: Optional[int] = None, dtype=jnp.bfloat16,
                 paged_impl: str = "auto", prefill_mode: str = "chunked",
                 chunk_pages: int = 2, token_budget: Optional[int] = None,
                 prefix_cache: bool = False, spec_decode: int = 0,
                 sampler: str = "greedy", temperature: float = 0.8,
                 top_p: float = 1.0, seed: int = 0,
                 spec_ngram_max: int = 3, spec_ngram_min: int = 2,
                 spec_gate: float = 1.5, spec_cooldown: int = 64):
        assert transformer.supports_paged(cfg), (
            f"paged decode needs full attention over token inputs: "
            f"pattern={cfg.pattern} (supported {transformer.PAGED_PATTERNS}),"
            f" sliding_window={cfg.sliding_window} (need 0), "
            f"frontend={cfg.frontend!r} (need 'tokens')")
        assert kv_bits in (16, 8, 4), \
            f"kv_bits must be 16, 8 or 4 (packed int4); got {kv_bits}"
        self.params = params
        self.cfg = cfg
        self.page_size = page_size
        self.eos_id = eos_id
        # "auto": the paged Pallas kernels on TPU, the jnp oracle elsewhere
        self.paged_impl = paged_impl = ops.resolve_impl(paged_impl)
        self.max_pages_per_seq = -(-max_seq_len // page_size)
        if n_pages is None:
            n_pages = 1 + max_batch * self.max_pages_per_seq
        self.pools = transformer.init_paged_pools(
            cfg, n_pages, page_size, kv_bits, dtype)
        assert prefill_mode in ("chunked", "legacy"), prefill_mode
        assert not (prefix_cache and prefill_mode == "legacy"), \
            "prefix caching needs chunked prefill (one-shot prefill would " \
            "rewrite shared pages)"
        self.prefix_cache = prefix_cache
        self.sched = PagedScheduler(
            n_slots=max_batch, n_pages=n_pages, page_size=page_size,
            max_pages_per_seq=self.max_pages_per_seq,
            prefix_cache=prefix_cache)
        self.prefill_mode = prefill_mode
        self.chunk_tokens = chunk_pages * page_size
        if self.chunk_tokens > max_seq_len:
            raise ValueError(
                f"chunk_pages {chunk_pages} x page_size {page_size} exceeds "
                f"max_seq_len {max_seq_len}")
        self.window_pages = chunk_window_pages(self.chunk_tokens, page_size)
        # token budget per mixed step: decode lanes cost 1 token each, a
        # prefill chunk costs chunk_tokens; default = one chunk + all lanes
        self.token_budget = (token_budget if token_budget is not None
                             else self.chunk_tokens + max_batch)
        assert sampler in ("greedy", "temperature"), sampler
        assert spec_decode >= 0, spec_decode
        assert not (spec_decode and prefill_mode == "legacy"), \
            "speculative decoding needs chunked prefill (the verify step " \
            "reuses the chunk-attention machinery)"
        self.sampler = sampler
        self.temperature = temperature
        self.top_p = top_p
        self._key = jax.random.PRNGKey(seed)
        self.spec_k = spec_decode
        self.spec_tokens = spec_decode + 1          # window width k+1
        self.spec_window_pages = verify_window_pages(self.spec_tokens,
                                                     page_size)
        self._drafter = NgramDrafter(max(1, spec_decode),
                                     ngram_max=spec_ngram_max,
                                     ngram_min=spec_ngram_min)
        self.spec_gate = spec_gate
        self.spec_cooldown = spec_cooldown
        self._spec_off = 0                          # cooldown steps left
        self._gate_cool = 2                         # doubles per miss streak
        self._gate_misses = 0                       # consecutive thin-draft steps
        self._acc_est = 0.5                         # per-proposal EMA, optimistic
        self._last_tok = np.zeros(max_batch, np.int32)
        self._requests: Dict[int, Request] = {}
        self.telemetry = telemetry.Recorder(self.sched)
        self._policies: Dict[int, cot.StopPolicy] = {}
        self._next_rid = 0
        self.steps_run = 0
        self.decode_tokens = 0
        self.mixed_steps = 0
        self.prefill_tokens = 0
        self.spec_steps = 0
        self.draft_tokens = 0
        self.accepted_tokens = 0

        self._prefill = jax.jit(
            partial(transformer.prefill, cfg=cfg, qcfg=qcfg, impl=impl,
                    kv_bits=16, dtype=dtype),
            static_argnames=("max_len",))
        # The step programs are named (serve_decode, serve_mixed,
        # serve_verify, serve_sample) so that traces and compile logs show
        # which one ran. The pool buffers are donated into every
        # steady-state program: each step rewrites a page or two of
        # multi-MB pools, and without input-output aliasing XLA copies
        # every pool leaf per step. All call sites immediately rebind
        # self.pools to the returned pools.
        def serve_decode(params, pools, page_table, tokens, pos):
            return transformer.decode_step_paged(
                params, pools, page_table, tokens, pos, cfg, qcfg=qcfg,
                impl=impl, paged_impl=paged_impl, dtype=dtype)

        def serve_mixed(params, pools, page_table, window_rows, tokens,
                        q_start, n_new):
            return transformer.prefill_chunk_paged(
                params, pools, page_table, window_rows, tokens, q_start,
                n_new, cfg, qcfg=qcfg, impl=impl, paged_impl=paged_impl,
                dtype=dtype)

        def serve_sample(logits):
            return jnp.argmax(logits, -1).astype(jnp.int32)

        self._decode = jax.jit(serve_decode, donate_argnums=(1,))
        self._mixed = jax.jit(serve_mixed, donate_argnums=(1,))
        self._sample = jax.jit(serve_sample)
        self._sample_t = jax.jit(partial(sampling.top_p, p=top_p,
                                         temp=temperature))

        def serve_verify(params, pools, page_table, window_rows, tokens,
                         q_start, n_new, key):
            # score the whole draft window read-only (the window's raw K/V
            # is spliced into the attention read, so a rejected suffix
            # never touches the pool), accept a prefix, then commit only
            # the accepted tokens through the fused quantize-on-write path
            from repro.serving import kv_pool
            logits, kv_win = transformer.verify_step_paged(
                params, pools, page_table, tokens, q_start, n_new, cfg,
                qcfg=qcfg, impl=impl, dtype=dtype)
            emit, acc = sampling.speculative_accept(
                logits.astype(jnp.float32), tokens, n_new, key,
                mode=self.sampler, temp=temperature, top_p=top_p)
            n_keep = jnp.where(n_new > 0, acc + 1, 0)
            out_pools = {}
            with jax.named_scope("kv_write"):
                for i in pools:
                    kw, vw = kv_win[i]
                    out_pools[i] = jax.vmap(
                        kv_pool.write_chunk,
                        in_axes=(0, 0, 0, None, None, None))(
                        pools[i], kw, vw, window_rows, q_start, n_keep)
            return emit, acc, out_pools

        self._verify = jax.jit(serve_verify, donate_argnums=(1,))
        self._zero_key = jax.random.PRNGKey(0)

        def to_pages(pools, caches, page_rows, lengths):
            from repro.serving import kv_pool
            new = dict(pools)
            for i, c in caches.items():
                new[i] = jax.vmap(kv_pool.write_prefill,
                                  in_axes=(0, 0, 0, None, None))(
                    pools[i], c["k"], c["v"], page_rows, lengths)
            return new

        self._to_pages = jax.jit(to_pages)

    # -- accounting -----------------------------------------------------------

    def kv_bytes_per_token(self) -> float:
        """Whole-model KV bytes per token slot (pages + scales, all blocks
        and groups)."""
        from repro.serving import kv_pool
        n_pages = self.sched.alloc.n_pages
        return sum(kv_pool.pool_bytes(p) for p in self.pools.values()) \
            / (n_pages * self.page_size)

    def compile_counts(self) -> Dict[str, int]:
        """Compilation-cache sizes of the jitted step functions. Chunked
        steady state is exactly {mixed: 1, decode: 1, prefill: 0,
        verify: 0}; --spec-decode adds at most one `verify` program
        (fixed k+1 window shape); legacy pays one `prefill` entry per
        distinct power-of-two page bucket."""
        return {"prefill": self._prefill._cache_size(),
                "mixed": self._mixed._cache_size(),
                "decode": self._decode._cache_size(),
                "verify": self._verify._cache_size()}

    def lower_steps(self) -> Dict[str, "jax.stages.Lowered"]:
        """The steady-state mixed and decode programs lowered at this
        engine's shapes, from the arguments `step` builds (here with no
        lane advancing), so that compiling them yields the programs serving
        runs (which kernels they call, their memory)."""
        mixed = self._mixed_args([], [])
        return {"mixed": self._mixed.lower(
                    self.params, self.pools, *map(jnp.asarray, mixed)),
                "decode": self._decode.lower(
                    self.params, self.pools, *self._decode_args())}

    def spec_stats(self) -> Dict[str, float]:
        """Cumulative speculative-decoding counters and acceptance rate
        (accepted drafter proposals / proposals scored; the bonus token
        every verify step emits is not counted on either side)."""
        return {"spec_steps": self.spec_steps,
                "draft_tokens": self.draft_tokens,
                "accepted_tokens": self.accepted_tokens,
                "acceptance_rate": (self.accepted_tokens
                                    / max(1, self.draft_tokens))}

    def prefix_cache_stats(self) -> Dict[str, float]:
        """Cumulative prefix-cache counters: prompt tokens through
        admission, tokens served from cached pages, the resulting hit
        rate, and the current cached-page census."""
        s = self.sched
        return {"prompt_tokens": s.prefix_prompt_tokens,
                "hit_tokens": s.prefix_hit_tokens,
                "hit_rate": (s.prefix_hit_tokens
                             / max(1, s.prefix_prompt_tokens)),
                "cached_pages": 0 if s.cache is None else s.cache.n_cached,
                "unreferenced_pages": (0 if s.cache is None
                                       else s.cache.n_unreferenced)}

    # -- request lifecycle ----------------------------------------------------

    def submit(self, prompt: Sequence[int], *, mode: str = "slow_think",
               max_new: int = 32) -> int:
        full = cot.apply_mode(prompt, mode, self.cfg.vocab)
        need = -(-len(full) // self.page_size)
        if need > self.sched.alloc.n_pages - 1:
            raise ValueError("prompt larger than the whole page pool")
        budget = cot.budget_for(mode, len(full), max_new)
        cap = self.max_pages_per_seq * self.page_size
        if len(full) + budget > cap:
            raise ValueError(
                f"prompt ({len(full)}) + budget ({budget}) exceeds "
                f"max_seq_len {cap}; raise max_seq_len or lower max_new")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=full, mode=mode, budget=budget,
                      t_submit=telemetry.clock())
        self._requests[rid] = req
        self.telemetry.track(req)
        self._policies[rid] = cot.policy_for(mode, len(full), max_new,
                                             eos_id=self.eos_id)
        self.sched.submit(req)
        return rid

    def request(self, rid: int) -> telemetry.RequestView:
        """A frozen view of a request: the prompt as served, its tokens so
        far, its times (submit, first admission, first token, done) and
        counts (admissions, preemptions, prefill steps). A finished request
        stays readable until `telemetry.REQUEST_RING` newer ones finish."""
        return self.telemetry.request(rid)

    def _emit(self, st, slot: int, req: Request, tok: int, t: float) -> None:
        """Give `req`, in `slot`, its next token, read back by the
        `serve.wait` that ended at `t`."""
        req.out.append(tok)
        self._last_tok[slot] = tok
        if req.t_first is None:
            req.t_first = t
        if not st.emitted or st.emitted[-1] != req.rid:
            st.emitted.append(req.rid)

    def _complete(self, slot: int, t: float) -> None:
        req = self.sched.complete(slot)
        req.t_done = t
        self.telemetry.finish(req)

    def _prefill_one(self, st, slot: int, req: Request) -> None:
        """Legacy one-shot prefill, bucketed to the next power-of-two page
        count so the compile count is O(log max_seq_len) rather than one
        program per distinct prompt-page count."""
        span = self.telemetry.span
        page = self.page_size
        n = len(req.prompt)
        need = -(-n // page)
        bucket_pages = 1 << (need - 1).bit_length()
        bucket = bucket_pages * page
        with span("serve.inputs"):
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :n] = req.prompt
            lens = jnp.asarray([n], jnp.int32)
            toks = jnp.asarray(toks)
            # bucket rows beyond the prompt's allocation scatter into
            # scratch (write_prefill zeroes positions >= lengths, so the
            # writes are 0s)
            rows = np.full((1, bucket_pages), SCRATCH_PAGE, np.int32)
            rows[0, :need] = self.sched.page_table[slot, :need]
            rows = jnp.asarray(rows)
        with span("serve.launch"):
            logits, caches = self._prefill(
                self.params, {"tokens": toks, "lengths": lens},
                max_len=bucket)
            self.pools = self._to_pages(self.pools, caches, rows, lens)
        st.ran("mixed", [(slot, 0, n)], bucket)
        with span("serve.wait") as wait:
            tok = int(self._sample_tokens(logits)[0])
        with span("serve.commit"):
            self.prefill_tokens += n
            req.prefill_steps += 1
            self._emit(st, slot, req, tok, wait.t1)
            if self._policies[req.rid].done(req.out):
                self._complete(slot, wait.t1)

    def _sample_tokens(self, logits) -> np.ndarray:
        """Sample next tokens per lane under the engine's sampler: greedy
        argmax (keyless, deterministic — the path the CoT study measures)
        or temperature with nucleus filtering (top_p=1.0 disables it)."""
        if self.sampler == "greedy":
            return np.asarray(self._sample(logits))
        self._key, sub = jax.random.split(self._key)
        return np.asarray(self._sample_t(logits, sub))

    def step(self) -> bool:
        """One engine step. Returns whether any progress was made."""
        with self.telemetry.step() as st:
            if self.prefill_mode == "legacy":
                return self._step_legacy(st)
            return self._step_chunked(st)

    def _step_legacy(self, st) -> bool:
        """Admit + one-shot prefill per admission, then one batched decode."""
        sched = self.sched
        span = self.telemetry.span
        progressed = False
        while True:
            # re-admit after prefill-time completions free their slots
            with span("serve.admit"):
                admitted = sched.admit()
            if not admitted:
                break
            progressed = True
            for slot, req in admitted:
                self._prefill_one(st, slot, req)
        with span("serve.plan"):
            sched.ensure_decode_capacity()
        if not sched.active:
            return progressed
        with span("serve.inputs"):
            args = self._decode_args()
        with span("serve.launch"):
            logits, self.pools = self._decode(self.params, self.pools, *args)
        self.steps_run += 1
        st.ran("mixed" if st.lanes else "decode",
               [(s, int(sched.lengths[s]), 1) for s in sorted(sched.active)],
               sched.n_slots)
        with span("serve.wait") as wait:
            nxt = self._sample_tokens(logits)
        with span("serve.commit"):
            for slot in list(sched.active):
                req = sched.active[slot]
                sched.lengths[slot] += 1
                self._emit(st, slot, req, int(nxt[slot]), wait.t1)
                self.decode_tokens += 1
                if self._policies[req.rid].done(req.out):
                    self._complete(slot, wait.t1)
        return True

    # -- chunked prefill ------------------------------------------------------

    def _plan_chunked(self):
        """Pick this step's lanes and secure their pages. Preemption during
        growth can evict lanes already picked (including mid-prefill
        victims), so the plan is recomputed until a pass allocates without
        evicting. Returns (advancing prefill slots, decode slots)."""
        sched = self.sched
        c = self.chunk_tokens
        while True:
            prefilling = sched.prefilling_slots()
            decoding = sched.decoding_slots()
            budget_left = self.token_budget - len(decoding)
            n_adv = max(1, budget_left // c) if prefilling else 0
            advancing = prefilling[:n_adv]
            evicted = False
            for slot in decoding:
                if slot not in sched.active:
                    continue
                if sched.grow_to(slot, int(sched.lengths[slot]) + 1):
                    evicted = True
            for slot in advancing:
                if slot not in sched.active:
                    continue
                req = sched.active[slot]
                prog = int(sched.prefill_progress[slot])
                n_new = min(c, len(req.prompt) - prog)
                if sched.grow_to(slot, prog + n_new):
                    evicted = True
            if not evicted:
                advancing = [s for s in advancing if s in sched.active]
                decoding = [s for s in decoding if s in sched.active]
                return advancing, decoding

    def _decode_args(self):
        """The decode step's (page_table, tokens, pos) for every lane."""
        sched = self.sched
        return (jnp.asarray(sched.page_table), jnp.asarray(self._last_tok),
                jnp.asarray(sched.lengths))

    def _mixed_args(self, advancing, decoding):
        """The mixed step's host arrays (page_table, windows, tokens,
        q_start, n_new): the next prompt chunk of each advancing slot, one
        token for each decoding slot, idle lanes n_new = 0."""
        sched = self.sched
        page = self.page_size
        c, wc = self.chunk_tokens, self.window_pages
        b = sched.n_slots
        toks = np.zeros((b, c), np.int32)
        q_start = np.zeros(b, np.int32)
        n_new = np.zeros(b, np.int32)
        windows = np.full((b, wc), SCRATCH_PAGE, np.int32)

        def fill_window(slot, start):
            pidx0 = start // page
            row = sched.page_table[slot]
            take = min(wc, row.shape[0] - pidx0)
            windows[slot, :take] = row[pidx0:pidx0 + take]

        for slot in advancing:
            req = sched.active[slot]
            prog = int(sched.prefill_progress[slot])
            n = min(c, len(req.prompt) - prog)
            toks[slot, :n] = req.prompt[prog:prog + n]
            q_start[slot] = prog
            n_new[slot] = n
            fill_window(slot, prog)
        for slot in decoding:
            start = int(sched.lengths[slot])
            toks[slot, 0] = self._last_tok[slot]
            q_start[slot] = start
            n_new[slot] = 1
            fill_window(slot, start)
        return sched.page_table, windows, toks, q_start, n_new

    def _step_chunked(self, st) -> bool:
        """Admit lazily (first chunk's pages only), then run one fixed-shape
        mixed step: prompt chunks for advancing prefill slots, one token for
        each decode slot, idle lanes masked out with n_new = 0."""
        sched = self.sched
        span = self.telemetry.span
        with span("serve.admit"):
            progressed = bool(sched.admit(
                max_prefill_pages=self.chunk_tokens // self.page_size))
        if not sched.active:
            return progressed
        with span("serve.plan"):
            advancing, decoding = self._plan_chunked()

        b = sched.n_slots
        if not advancing:
            # pure-decode steady state: speculate when enabled and warm
            if self.spec_k and decoding:
                if self._spec_off > 0:
                    self._spec_off -= 1
                elif self._try_spec_step(st, decoding):
                    return True
            # steady-state decode: same compiled program as legacy decode
            with span("serve.inputs"):
                args = self._decode_args()
            with span("serve.launch"):
                logits, self.pools = self._decode(self.params, self.pools,
                                                  *args)
            self.steps_run += 1
            st.ran("decode", [(s, int(sched.lengths[s]), 1)
                              for s in decoding], b)
        else:
            with span("serve.inputs"):
                args = self._mixed_args(advancing, decoding)
                n_new = args[-1]
                dev_args = [jnp.asarray(a) for a in args]
            with span("serve.launch"):
                logits, self.pools = self._mixed(self.params, self.pools,
                                                 *dev_args)
            self.mixed_steps += 1
            q_start = args[-2]
            st.ran("mixed", [(s, int(q_start[s]), int(n_new[s]))
                             for s in range(b) if n_new[s] > 0],
                   b * self.chunk_tokens)

        with span("serve.wait") as wait:
            nxt = np.asarray(self._sample(logits))
        t = wait.t1
        with span("serve.commit"):
            for slot in advancing:
                req = sched.active[slot]
                n = int(n_new[slot])
                sched.prefill_progress[slot] += n
                sched.lengths[slot] += n
                self.prefill_tokens += n
                req.prefill_steps += 1
                if int(sched.prefill_progress[slot]) == len(req.prompt):
                    # prompt fully in cache: logits at its last token yield
                    # the first generated token (as legacy prefill does)
                    self._emit(st, slot, req, int(nxt[slot]), t)
                    if self._policies[req.rid].done(req.out):
                        self._complete(slot, t)
            for slot in decoding:
                req = sched.active[slot]
                sched.lengths[slot] += 1
                self._emit(st, slot, req, int(nxt[slot]), t)
                self.decode_tokens += 1
                if self._policies[req.rid].done(req.out):
                    self._complete(slot, t)
        return True

    # -- speculative decoding -------------------------------------------------

    def _try_spec_step(self, st, decoding: List[int]) -> bool:
        """One speculative verify step over the pure-decode lanes: draft up
        to k tokens per lane by prompt lookup, score the k+1-token windows
        read-only in the single jitted verify program, and commit each
        lane's accepted prefix + bonus token through the fused
        quantize-on-write path (rejected suffixes were never written).
        Returns False (caller falls through to the vanilla decode step)
        when the cost-model gate says drafting is too thin to pay for the
        verify — expected extra tokens (drafted total x the running
        acceptance estimate) below spec_gate per lane — so adversarial,
        n-gram-free workloads degrade to plain decode plus a cheap,
        exponentially backed-off host-side lookup.

        Lanes with no usable draft still ride the verify step with
        n_new = 1, which is bit-exact with a vanilla decode write
        (write_chunk with one token == write_token)."""
        sched = self.sched
        span = self.telemetry.span
        page = self.page_size
        cap = self.max_pages_per_seq * page
        cs, wcv = self.spec_tokens, self.spec_window_pages

        drafts: Dict[int, List[int]] = {}
        with span("serve.draft"):
            for slot in decoding:
                req = sched.active[slot]
                length = int(sched.lengths[slot])
                budget_left = self._policies[req.rid].budget - len(req.out)
                # the pending token costs one cache slot and one budget
                # slot; clamp so accept-all can neither overrun the
                # sequence cap nor outlive the stop policy's budget
                room = min(self.spec_k, budget_left - 1, cap - length - 1)
                drafts[slot] = (self._drafter.propose(
                    list(req.prompt) + list(req.out), k=room)
                    if room >= 1 else [])
        total = sum(len(d) for d in drafts.values())
        if total * self._acc_est < self.spec_gate * len(decoding):
            # expected extra tokens don't cover the verify's cost premium
            # over a plain decode step; after a few consecutive thin steps
            # stop even *drafting* for a while (the host-side lookup is
            # not free at decode-step latencies), doubling the pause up to
            # spec_cooldown so a persistently n-gram-free workload pays an
            # ever-smaller probing tax
            self._gate_misses += 1
            if self._gate_misses >= 2:
                self._spec_off = self._gate_cool
                self._gate_cool = min(self._gate_cool * 2,
                                      self.spec_cooldown)
                self._gate_misses = 0
            return False
        self._gate_misses = 0

        # secure pages for every lane's full window (pending + drafts);
        # growth can preempt lanes (including a drafting lane itself) —
        # replan until a pass allocates without evicting
        try:
            with span("serve.plan"):
                while True:
                    evicted = False
                    for slot in decoding:
                        if slot not in sched.active:
                            continue
                        target = (int(sched.lengths[slot]) + 1
                                  + len(drafts[slot]))
                        if sched.grow_to(slot, target):
                            evicted = True
                    if not evicted:
                        break
        except RuntimeError:
            # pool too tight for even one lane's window — surplus pages
            # already granted stay with their lanes (reused by later
            # growth, freed on completion); vanilla decode still fits
            # because _plan_chunked grew every lane for one token
            return False
        decoding = [s for s in decoding if s in sched.active]
        if not decoding:
            return False

        b = sched.n_slots
        with span("serve.inputs"):
            toks = np.zeros((b, cs), np.int32)
            q_start = np.zeros(b, np.int32)
            n_new = np.zeros(b, np.int32)
            windows = np.full((b, wcv), SCRATCH_PAGE, np.int32)
            for slot in decoding:
                d = drafts[slot]
                start = int(sched.lengths[slot])
                toks[slot, 0] = self._last_tok[slot]
                toks[slot, 1:1 + len(d)] = d
                q_start[slot] = start
                n_new[slot] = 1 + len(d)
                pidx0 = start // page
                row = sched.page_table[slot]
                take = min(wcv, row.shape[0] - pidx0)
                windows[slot, :take] = row[pidx0:pidx0 + take]
            if self.sampler == "greedy":
                key = self._zero_key
            else:
                self._key, key = jax.random.split(self._key)
            dev_args = [jnp.asarray(a) for a in (sched.page_table, windows,
                                                 toks, q_start, n_new)]
        with span("serve.launch"):
            emit, acc, self.pools = self._verify(self.params, self.pools,
                                                 *dev_args, key)
        self.spec_steps += 1
        st.ran("verify", [(s, int(q_start[s]), int(n_new[s]))
                          for s in decoding], b * cs)
        with span("serve.wait") as wait:
            emit, acc = np.asarray(emit), np.asarray(acc)
        t = wait.t1

        step_scored = step_accepted = 0
        with span("serve.commit"):
            for slot in decoding:
                req = sched.active[slot]
                a = int(acc[slot])
                self.draft_tokens += int(n_new[slot]) - 1
                self.accepted_tokens += a
                step_scored += int(n_new[slot]) - 1
                step_accepted += a
                new_len = int(q_start[slot]) + 1 + a
                sched.lengths[slot] = new_len
                sched.truncate_to(slot, new_len)
                for j in range(a + 1):
                    self._emit(st, slot, req, int(emit[slot, j]), t)
                    self.decode_tokens += 1
                    if self._policies[req.rid].done(req.out):
                        self._complete(slot, t)
                        break
        # per-proposal acceptance EMA feeding the gate; the 0.2 floor
        # keeps a cold streak from pinning the gate shut forever (the
        # doubling cooldown, not the EMA, owns long-horizon backoff)
        rate = step_accepted / max(1, step_scored)
        self._acc_est = min(1.0, max(0.2, 0.8 * self._acc_est + 0.2 * rate))
        if rate >= 0.25:
            # a verify that actually paid off restarts the cooldown ladder
            # from the bottom
            self._gate_cool = 2
        else:
            # one that didn't was a false positive from a coincidental
            # n-gram hit — climb the ladder immediately rather than waiting
            # for thin-draft misses, so an adversarial workload's wasted
            # verifies (the costliest false-positive mode) back off just
            # as fast as its wasted drafting
            self._spec_off = self._gate_cool
            self._gate_cool = min(self._gate_cool * 2, self.spec_cooldown)
        return True

    def run(self, prompts: Sequence[Sequence[int]], *,
            mode: str = "slow_think", max_new: int = 32,
            max_steps: int = 100_000) -> ContinuousResult:
        rids = [self.submit(p, mode=mode, max_new=max_new) for p in prompts]
        # fresh speculation heuristics per batch run: leftover cooldown or
        # window state from a previous batch would make identical runs
        # gate differently (submit()/step() callers keep continuous state)
        self._spec_off = self._gate_misses = 0
        self._gate_cool = 2
        self._acc_est = 0.5
        steps0, tokens0 = self.steps_run, self.decode_tokens
        evict0 = self.sched.n_evictions
        mixed0, pf0 = self.mixed_steps, self.prefill_tokens
        hit0 = self.sched.prefix_hit_tokens
        spec0, dr0, acc0 = (self.spec_steps, self.draft_tokens,
                            self.accepted_tokens)
        steps = 0
        while not self.sched.idle:
            progressed = self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("continuous engine exceeded max_steps")
            if not progressed and not self.sched.idle:
                raise RuntimeError("scheduler stalled with pending work")
        reqs = [self._requests[r] for r in rids]
        return ContinuousResult(
            tokens=[r.out for r in reqs],
            modes=[r.mode for r in reqs],
            prompt_lens=[len(r.prompt) for r in reqs],
            steps_run=self.steps_run - steps0,
            decode_tokens=self.decode_tokens - tokens0,
            evictions=self.sched.n_evictions - evict0,
            mixed_steps=self.mixed_steps - mixed0,
            prefill_tokens=self.prefill_tokens - pf0,
            prefix_hit_tokens=self.sched.prefix_hit_tokens - hit0,
            spec_steps=self.spec_steps - spec0,
            draft_tokens=self.draft_tokens - dr0,
            accepted_tokens=self.accepted_tokens - acc0)
