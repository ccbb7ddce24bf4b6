"""paged_decode_attention: one query per lane against the lane's pages.
Needed per lane of context `n` keys: the q.k and p.v products
(4 nq hd n operations) and one read of the lane's keys, values and page
scales, with the query read and the output written in bf16. One call per
layer in each decode step."""
from __future__ import annotations

import math

from bench.counts import kv_bytes_per_token, scale_bytes_per_page


def call(model: dict, kv_bits: int, page: int, contexts):
    nq, hd = model["num_attention_heads"], model["head_dim"]
    ops = sum(4 * nq * hd * n for n in contexts)
    nbytes = sum(n * kv_bytes_per_token(model, kv_bits)
                 + math.ceil(n / page) * scale_bytes_per_page(model, kv_bits)
                 + 2 * 2 * nq * hd for n in contexts)
    return ops, nbytes


def calls(model: dict, quant: dict, step):
    if step.kind != "decode":
        return []
    contexts = [q_start + n for q_start, n in step.lanes]
    one = call(model, quant["kv_bits"], quant["page_size"], contexts)
    return [one] * model["num_hidden_layers"]
