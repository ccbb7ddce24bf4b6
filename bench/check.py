"""The comparison that decides `correct` for a served model.

After the window, a sample of the requests the run finished (drawn from the
seed, the one with the most served tokens always in it) is run through the
plain reference (`bench/reference.py`, with the layers of the
configuration's architecture) over its prompt as served and its served
tokens. At each served token, the gap by which the reference's logit of
that token lies below the reference's best logit is read; the run is
correct when every finished request has exactly the tokens it asked for and
the widest gap is within the configuration's limit.

The control is the reference at the configuration's `control` precision,
put in the program's place: at each position of the same sequences, the
token it ranks first is judged by the same gap against the same limit, so a
run in control mode (`bench/control.py`) is not correct.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import reference


SAMPLE = 4               # finished requests run through the reference


def sample(finished: List[dict], seed: int, k: int = SAMPLE) -> List[dict]:
    """k finished requests drawn from the seed, the longest output in it."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-len(r["served"]), r["rid"]))
    rest = order[1:]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [order[0]] + [rest[i] for i in sorted(pick)]


def gaps(arch, seed: int, spec: dict, reqs: List[dict],
         control: bool = False) -> Dict[str, float]:
    """Widest gap of the served tokens under the reference of architecture
    `arch` (`logit_gap`), and with `control`, the widest gap of the
    control's first choices (`control_gap`)."""
    m, page = spec["model"], spec["engine"]["page_size"]
    padded = reference.padded_len(spec["engine"]["max_seq_len"])
    prec = {k: spec["quant"][k] for k in
            ("weight_bits", "weight_group", "act_bits", "kv_bits")}
    worst, worst_ctl, n = 0.0, 0.0, 0
    for r in reqs:
        judge = ()
        if control:
            low = reference.logits_summary(arch, seed, m, spec["control"],
                                           page, padded, r["prompt"],
                                           r["served"])
            judge = low["argmax"]
        ref = reference.logits_summary(arch, seed, m, prec, page, padded,
                                       r["prompt"], r["served"], judge)
        worst = max(worst, float(np.max(ref["best"] - ref["served"])))
        if control:
            worst_ctl = max(worst_ctl,
                            float(np.max(ref["best"] - ref["judged"])))
        n += len(r["served"])
    out = {"logit_gap": worst, "tokens_compared": n}
    if control:
        out["control_gap"] = worst_ctl
    return out
