"""The one module that calls into the program under test.

Touch points on the program (kept by later changes, or changed together
with the benchmark):

  repro.configs.base.ArchConfig               the model's sizes
  repro.core.quant.qtypes.preset              the quantization preset
  repro.core.quant.ptq.quantize_model         post-training quantization
  repro.serving.engine.ContinuousBatchingEngine(...), .submit, .step
  engine counters: steps_run, mixed_steps (which program a step ran)
  repro.serving.cot.budget_for                a mode's output budget
  private state read for per-request timing:  engine._requests[rid].out
      and .prompt, sched.active (slot -> request), sched.waiting,
      sched.lengths

Weights are made and quantized in one jitted call on the device: the
float32 layers come from `bench/weights.py` one at a time inside the call
and go straight through the program's PTQ, so the float32 copy of the
blocks is never whole on the chip.
"""
from __future__ import annotations

import dataclasses
import gc
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from bench import weights

from repro.configs.base import ArchConfig
from repro.core.quant import ptq
from repro.core.quant.qtypes import preset
from repro.serving import cot
from repro.serving.engine import ContinuousBatchingEngine

# The program's quantization sites per layer, and the tree it serves from.
_LINEARS = {"wqkv": ("attn", "wqkv"), "wo": ("attn", "wo"),
            "w_in": ("mlp", "w_in"), "w_out": ("mlp", "w_out")}


def arch_config(name: str, m: dict) -> ArchConfig:
    return ArchConfig(
        name=name, family="dense", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        tie_embeddings=m["tie_word_embeddings"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], act="swiglu")


def _program_layer(w: dict) -> dict:
    tree = {"ln1": {"g": w["ln1"]}, "ln2": {"g": w["ln2"]},
            "attn": {}, "mlp": {}}
    for name, (part, leaf) in _LINEARS.items():
        tree[part][leaf] = {"w": w[name]}
    return tree


def make_params(cfg: ArchConfig, m: dict, quant: str, seed: int):
    """The served parameter tree, from the seed, in one jitted call."""
    qcfg = preset(quant)
    one = dataclasses.replace(cfg, n_layers=1)

    def quantized_layer(key, i):
        tree = _program_layer(weights.layer(key, i, m))
        if qcfg is None:
            return tree
        return ptq.quantize_model({"blocks": {"0": tree}}, one, qcfg)[
            "blocks"]["0"]

    @jax.jit
    def build(key):
        blocks = jax.lax.map(lambda i: quantized_layer(key, i),
                             jnp.arange(cfg.n_layers))
        params = {"embed": {"w": weights.embed(key, m)},
                  "blocks": {"0": blocks},
                  "final_norm": {"g": weights.final_norm(key, m)}}
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": weights.lm_head(key, m)}
        return params

    return build(weights.base_key(seed)), qcfg


@dataclasses.dataclass
class StepInfo:
    kind: Optional[str]                  # "mixed", "decode" or None (idle)
    lanes: List[Tuple[int, int]]         # (q_start, n_new) of working lanes
    emitted: List[int]                   # rids that got a new token


class Server:
    """The program's continuous-batching engine, built from a configuration
    file's `engine` and `quant` sections and the seed."""

    def __init__(self, name: str, spec: dict, seed: int):
        m, e = spec["model"], spec["engine"]
        cfg = arch_config(name, m)
        params, qcfg = make_params(cfg, m, spec["quant"]["weights"], seed)
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

    def out(self, rid: int) -> List[int]:
        return self.engine._requests[rid].out

    def prompt(self, rid: int) -> List[int]:
        """The prompt as served, the mode's directive token included."""
        return list(self.engine._requests[rid].prompt)

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
            grew = len(e._requests[rid].out) > out0
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
