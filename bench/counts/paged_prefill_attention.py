"""paged_prefill_attention: a lane's n_new queries at q_start.. attend
causally to its pages (decode lanes ride along with n_new = 1). Needed per
lane: the q.k and p.v products over each query's keys, and one read of the
lane's keys, values and page scales up to q_start + n_new, with the queries
read and the outputs written in bf16. One call per layer in each mixed
step."""
from __future__ import annotations

import math

from bench.counts import context_keys, kv_bytes_per_token, \
    scale_bytes_per_page


def call(model: dict, kv_bits: int, page: int, lanes):
    nq, hd = model["num_attention_heads"], model["head_dim"]
    ops = sum(4 * nq * hd * context_keys(q0, n) for q0, n in lanes)
    nbytes = sum((q0 + n) * kv_bytes_per_token(model, kv_bits)
                 + math.ceil((q0 + n) / page)
                 * scale_bytes_per_page(model, kv_bits)
                 + n * 2 * 2 * nq * hd for q0, n in lanes)
    return ops, nbytes


def calls(model: dict, quant: dict, step):
    if step.kind != "mixed":
        return []
    one = call(model, quant["kv_bits"], quant["page_size"], step.lanes)
    return [one] * model["num_hidden_layers"]
