"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), all in seconds-per-step on the
per-chip peaks of hw.py (v5e for the dry-run meshes):

  compute    = HLO FLOPs / peak            (int8 cells: linear-GEMM FLOPs at
                                            the int8 peak, rest at bf16)
  memory     = HLO bytes accessed / HBM bw
  collective = collective bytes / ICI link bw

`cost_analysis()` numbers are per-device (the SPMD-partitioned module), so
terms divide by per-chip peaks directly. Collective bytes are parsed from
the partitioned HLO text: we record each all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute with its operand bytes and
replica-group size, and report both the raw operand sum (the assignment's
definition) and a ring-adjusted estimate (bytes actually crossing links:
all-gather moves (n-1)x its operand shard, all-reduce ~2x(n-1)/n, etc.),
using the adjusted figure for the term.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

from repro.roofline import hw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\})")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


@dataclasses.dataclass
class Collective:
    op: str
    operand_bytes: int
    group_size: int

    @property
    def link_bytes(self) -> int:
        """Ring-algorithm bytes crossing each chip's links."""
        n = max(self.group_size, 1)
        if n == 1:
            return 0
        if self.op.startswith("all-gather"):
            return self.operand_bytes * (n - 1)
        if self.op.startswith("all-reduce"):
            return int(2 * self.operand_bytes * (n - 1) / n)
        if self.op.startswith("reduce-scatter"):
            return int(self.operand_bytes * (n - 1) / n)
        if self.op.startswith("all-to-all"):
            return int(self.operand_bytes * (n - 1) / n)
        return self.operand_bytes  # collective-permute


def parse_collectives(hlo_text: str) -> List[Collective]:
    out = []
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"= [a-z0-9\[\],() ]*?(all-gather|all-reduce|"
                      r"reduce-scatter|all-to-all|collective-permute)"
                      r"(-start)?\(", stripped)
        if not m:
            continue
        op = m.group(1)
        # operand shapes: everything inside the call parens
        call = stripped[m.end():]
        operand_bytes = sum(_shape_bytes(d, s)
                            for d, s in _SHAPE_RE.findall(call))
        g = _GROUPS_RE.search(stripped)
        if g:
            group_size = g.group(1).count(",") + 1
        else:
            gi = _GROUPS_IOTA_RE.search(stripped)
            group_size = int(gi.group(2)) if gi else 1
        out.append(Collective(op, operand_bytes, group_size))
    return out


def collective_summary(colls: List[Collective]) -> Dict:
    by_op: Dict[str, Dict] = {}
    for c in colls:
        d = by_op.setdefault(c.op, {"count": 0, "operand_bytes": 0,
                                    "link_bytes": 0})
        d["count"] += 1
        d["operand_bytes"] += c.operand_bytes
        d["link_bytes"] += c.link_bytes
    return {
        "by_op": by_op,
        "total_operand_bytes": sum(c.operand_bytes for c in colls),
        "total_link_bytes": sum(c.link_bytes for c in colls),
        "count": len(colls),
    }


# ---------------------------------------------------------------------------
# Analytic model FLOPs (assignment formulas)
# ---------------------------------------------------------------------------

def model_flops(cfg, shape_kind: str, seq_len: int, global_batch: int) -> Dict:
    """MODEL_FLOPS per the assignment: 6*N*D train (N=params; N_active for
    MoE), 2*N*D forward-only prefill, 2*N*B decode (one token). Also returns
    the analytic *linear-GEMM* forward FLOPs used to split the int8/bf16
    compute peaks."""
    n_active = cfg.active_param_count()
    if shape_kind == "train":
        d_tokens = seq_len * global_batch
        total = 6 * n_active * d_tokens
        lin_fwd = 2 * n_active * d_tokens
    elif shape_kind == "prefill":
        d_tokens = seq_len * global_batch
        total = 2 * n_active * d_tokens
        lin_fwd = total
    else:  # decode: one token per request
        d_tokens = global_batch
        total = 2 * n_active * d_tokens
        lin_fwd = total
    # attention score/value FLOPs (forward), causal halved; SWA capped
    attn = 0
    n_attn_layers = sum(1 for b in cfg.pattern
                        if b in ("self", "moe", "cross", "hybrid"))
    n_attn_layers *= cfg.n_groups
    if n_attn_layers and cfg.n_heads:
        kv_len = seq_len if shape_kind != "decode" else seq_len
        if cfg.sliding_window:
            kv_len = min(kv_len, cfg.sliding_window)
        q_len = seq_len if shape_kind != "decode" else 1
        per_layer = 4 * global_batch * q_len * kv_len * cfg.n_heads * cfg.hd
        if shape_kind != "decode" and not cfg.sliding_window:
            per_layer //= 2  # causal
        attn = per_layer * n_attn_layers
        if shape_kind == "train":
            attn *= 3  # fwd + bwd
    return {"model_flops": total, "linear_fwd_flops": lin_fwd,
            "attn_flops": attn, "tokens": d_tokens}


# ---------------------------------------------------------------------------
# Terms
# ---------------------------------------------------------------------------

def roofline_terms(*, hlo_flops_per_dev: float, hlo_bytes_per_dev: float,
                   link_bytes_per_dev: float, n_chips: int,
                   int8_linear_flops_global: float = 0.0) -> Dict:
    """All inputs per-device except int8_linear_flops_global (analytic,
    divided by chips here). Peaks are v5e's: the dry-run's meshes are v5e."""
    pk = hw.peaks(hw.V5E)
    int8_per_dev = min(int8_linear_flops_global / n_chips, hlo_flops_per_dev)
    bf16_per_dev = hlo_flops_per_dev - int8_per_dev
    compute = bf16_per_dev / pk.bf16_flops + int8_per_dev / pk.int8_ops
    memory = hlo_bytes_per_dev / pk.hbm_bw
    collective = link_bytes_per_dev / pk.ici_bw
    terms = {"compute_s": compute, "memory_s": memory,
             "collective_s": collective}
    dom = max(terms, key=terms.get)
    bound = max(compute, memory, collective)
    terms.update({
        "dominant": dom,
        "step_s_lower_bound": bound,
        "roofline_fraction": compute / bound if bound > 0 else 0.0,
    })
    return terms
