"""paged_decode_attention: one query per lane against the lane's pages.
Needed per lane of context `n` keys: the architecture's operations per key
for each of them (q.k and p.v) and one read of the lane's cache and page
scales, with the query read and the output written. One call per attention
layer in each decode step."""
from __future__ import annotations

import math


def call(arch, model: dict, kv_bits: int, page: int, contexts):
    per_key = arch.attention_ops_per_key(model)
    ops = sum(per_key * n for n in contexts)
    nbytes = sum(n * arch.kv_bytes_per_token(model, kv_bits)
                 + math.ceil(n / page)
                 * arch.scale_bytes_per_page(model, kv_bits)
                 + arch.query_bytes(model) for n in contexts)
    return ops, nbytes


def calls(arch, model: dict, quant: dict, step):
    if step.kind != "decode":
        return []
    contexts = [q_start + n for q_start, n in step.lanes]
    one = call(arch, model, quant["kv_bits"], quant["page_size"], contexts)
    return [one] * arch.attention_layers(model)
