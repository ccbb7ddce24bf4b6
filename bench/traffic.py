"""The one generator of traffic. A traffic mix is a data file,
`bench/traffic/<name>.json`, of parameters that this module reads:

  loop            "open": requests arrive on a Poisson schedule at
                  `rate_per_s`, whatever the server does, after `warm_s`
                  seconds of the same load before the window;
                  "closed": `sessions` clients (an int, or "max_batch" for
                  one per lane), each sending its next request as soon as
                  the last one finished
  arrivals        open loop, optional: {"process": "poisson"} (the default)
                  or {"process": "bursts", "size": k}: k requests due at
                  once, the bursts on a Poisson schedule at rate_per_s / k
  mode            the reasoning mode every request asks for
  prompt_tokens   a length distribution: {"dist": "lognormal", "median",
  output_tokens   "sigma", "min", "max"} or {"dist": "uniform", "min", "max"}
  shared_prefix   optional: {"groups": g, "tokens": <a length distribution>}:
                  each prompt begins with the prefix of one of g groups (the
                  same ids for every request of the group), then its own
                  prompt_tokens; requests are dealt to the groups in turn,
                  in an order drawn from `schedule_seed`
  staggered       closed loop: each first request starts part-way through its
                  output, a uniform share of it already in the cache, built
                  in set-up as part of its prompt
  schedule_seed   orders the sizes and the gaps between arrivals

Every run gets the same sizes and arrivals: lengths and gaps are the
quantiles of their distributions at (i + 1/2) / n, put in an order drawn
from the mix's `schedule_seed`. The run's seed draws the token ids (and the
weights), so runs with different seeds do the same work on other inputs.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import List

import numpy as np

RESERVED_TOP_IDS = 8      # the top of the vocabulary is kept for directives


@dataclasses.dataclass
class Request:
    due: float                 # seconds after the schedule's start
    prompt: List[int]
    n_out: int                 # output tokens the request stops at
    done_before: int = 0       # closed loop: output tokens already in prompt


def load(root: str, name: str) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 1/2) / n of `dist`, in order."""
    u = _grid(n)
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.round(x), dist["min"], dist["max"]).astype(int)


def _tokens(rng, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab - RESERVED_TOP_IDS, n).tolist()


class _Prompts:
    """Prompt ids from the run's seed: the shared prefixes of the mix's
    groups first (if it has any), then each request's own ids."""

    def __init__(self, mix: dict, seed: int, n: int, vocab: int, order):
        self.rng, self.vocab = np.random.default_rng(seed), vocab
        shared = mix.get("shared_prefix")
        self.prefixes, self.group = [[]], np.zeros(n, int)
        if shared:
            g = shared["groups"]
            sizes = lengths(shared["tokens"], g)[order.permutation(g)]
            self.prefixes = [_tokens(self.rng, int(k), vocab) for k in sizes]
            self.group = (np.arange(n) % g)[order.permutation(n)]

    def __call__(self, i: int, own: int) -> List[int]:
        return (self.prefixes[self.group[i]]
                + _tokens(self.rng, own, self.vocab))


def _due_times(mix: dict, n: int, order) -> np.ndarray:
    """Due times of n requests, from the quantiles of the gaps between
    arrivals (or between bursts), in an order drawn from `order`."""
    arr = mix.get("arrivals", {"process": "poisson"})
    size = {"poisson": 1, "bursts": arr.get("size", 1)}.get(arr["process"])
    if size is None:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    n_bursts = -(-n // size)
    gaps = -np.log1p(-_grid(n_bursts)) * size / mix["rate_per_s"]
    starts = np.cumsum(gaps[order.permutation(n_bursts)]) - gaps.mean()
    return np.repeat(starts, size)[:n]


def open_loop(mix: dict, seed: int, seconds: float, vocab: int,
              drain_s: float = 0.0) -> List[Request]:
    """Requests due over the warm period, the window and `drain_s` after."""
    rate = mix["rate_per_s"]
    n = int(math.ceil(rate * (mix["warm_s"] + seconds + drain_s))) + 1
    order = np.random.default_rng(mix["schedule_seed"])
    p = lengths(mix["prompt_tokens"], n)[order.permutation(n)]
    o = lengths(mix["output_tokens"], n)[order.permutation(n)]
    due = _due_times(mix, n, order)
    prompts = _Prompts(mix, seed, n, vocab, order)
    return [Request(float(due[i]), prompts(i, int(p[i])), int(o[i]))
            for i in range(n)]


def closed_loop(mix: dict, seed: int, sessions: int, vocab: int,
                rounds: int = 3) -> List[Request]:
    """`sessions` first requests (staggered) then the ones that replace
    them, `rounds` per client in all."""
    n = sessions * rounds
    order = np.random.default_rng(mix["schedule_seed"])
    p = lengths(mix["prompt_tokens"], n)[order.permutation(n)]
    o = lengths(mix["output_tokens"], n)[order.permutation(n)]
    share = _grid(sessions)[order.permutation(sessions)]
    prompts = _Prompts(mix, seed, n, vocab, order)
    out = []
    for i in range(n):
        before = (int(share[i] * o[i]) if i < sessions and mix["staggered"]
                  else 0)
        out.append(Request(0.0, prompts(i, int(p[i]) + before),
                           int(o[i]) - before, before))
    return out
