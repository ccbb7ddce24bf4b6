"""The one module that calls into the program's serving engine; the
architecture's package (`bench/arch/<arch>/`) maps a configuration onto the
program's `ArchConfig` and parameter tree.

Touch points on the program (kept by later changes, or changed together
with the benchmark):

  repro.core.quant.qtypes.preset              the quantization preset
  repro.serving.engine.ContinuousBatchingEngine(...), .submit, .step
  engine counters: steps_run, mixed_steps (which program a step ran)
  engine.request(rid)                         a request's prompt as served
                                              and its tokens
  repro.serving.cot.budget_for                a mode's output budget
  private state read for per-step lanes:      sched.active (slot ->
      request), sched.waiting, sched.lengths
  and, through the architecture: repro.configs.base.ArchConfig and
  repro.core.quant.ptq.quantize_model (post-training quantization).
"""
from __future__ import annotations

import dataclasses
import gc
import math
from typing import List, Optional, Tuple

from repro.core.quant.qtypes import preset
from repro.serving import cot
from repro.serving.engine import ContinuousBatchingEngine


@dataclasses.dataclass
class StepInfo:
    kind: Optional[str]                  # "mixed", "decode" or None (idle)
    lanes: List[Tuple[int, int]]         # (q_start, n_new) of working lanes
    emitted: List[int]                   # rids that got a new token


class Server:
    """The program's continuous-batching engine, built from a configuration
    file's `model`, `engine` and `quant` sections, its architecture `arch`
    (`bench.run.load_arch`) and the seed."""

    def __init__(self, name: str, spec: dict, seed: int, arch):
        m, e = spec["model"], spec["engine"]
        cfg = arch.arch_config(name, m)
        qcfg = preset(spec["quant"]["weights"])
        params = arch.make_params(cfg, m, qcfg, seed)
        self.engine = ContinuousBatchingEngine(
            params, cfg, qcfg=qcfg, kv_bits=spec["quant"]["kv_bits"],
            page_size=e["page_size"], max_batch=e["max_batch"],
            max_seq_len=e["max_seq_len"], n_pages=e["n_pages"],
            chunk_pages=e["chunk_pages"], token_budget=e["token_budget"],
            prefix_cache=e["prefix_cache"], seed=seed)

    # -- requests ------------------------------------------------------------

    def submit(self, prompt: List[int], n_out: int, mode: str) -> int:
        """Queue a request that stops after exactly `n_out` tokens."""
        full = len(prompt) + 1                  # the mode's directive token
        frac = cot.POLICIES[mode].budget_frac
        max_new = math.ceil(n_out / frac)
        while cot.budget_for(mode, full, max_new) < n_out:
            max_new += 1
        if cot.budget_for(mode, full, max_new) != n_out:
            raise ValueError(f"mode {mode} cannot stop at {n_out} tokens")
        return self.engine.submit(prompt, mode=mode, max_new=max_new)

    def out(self, rid: int) -> Tuple[int, ...]:
        return self.engine.request(rid).out

    def prompt(self, rid: int) -> List[int]:
        """The prompt as served, the mode's directive token included."""
        return list(self.engine.request(rid).prompt)

    def active_rids(self) -> List[int]:
        return [r.rid for r in self.engine.sched.active.values()]

    def held_tokens(self) -> int:
        """Tokens in the caches of the requests in lanes."""
        sched = self.engine.sched
        return sum(int(sched.lengths[s]) for s in sched.active)

    def waiting(self) -> int:
        return len(self.engine.sched.waiting)

    def step(self) -> StepInfo:
        """One engine step, with the lanes it worked on (from the scheduler's
        lengths before and after) and the requests it gave a token."""
        e, sched = self.engine, self.engine.sched
        before = {s: (r.rid, int(sched.lengths[s]), len(r.prompt), len(r.out))
                  for s, r in sched.active.items()}
        mixed0, decode0 = e.mixed_steps, e.steps_run
        e.step()
        kind = ("mixed" if e.mixed_steps > mixed0 else
                "decode" if e.steps_run > decode0 else None)
        lanes, emitted = [], []
        if kind is None:
            return StepInfo(None, lanes, emitted)
        now = {r.rid: (s, r) for s, r in sched.active.items()}
        for s, (rid, len0, plen, out0) in before.items():
            out = now[rid][1].out if rid in now else self.out(rid)
            grew = len(out) > out0
            if rid in now:               # still in its slot
                n = int(sched.lengths[s]) - len0
            elif grew:                   # finished this step
                n = 1 if len0 >= plen else plen - len0
            else:                        # preempted before the compute
                n = 0
            if n > 0:
                lanes.append((len0, n))
            if grew:
                emitted.append(rid)
        old = {b[0] for b in before.values()}
        for rid, (s, r) in now.items():  # admitted this step
            if rid not in old:
                n = min(int(sched.lengths[s]), e.chunk_tokens)
                lanes.append((int(sched.lengths[s]) - n, n))
                if r.out:
                    emitted.append(rid)
        return StepInfo(kind, lanes, emitted)

    def close(self) -> None:
        """Free the program's device state (weights, pools)."""
        self.engine = None
        gc.collect()
