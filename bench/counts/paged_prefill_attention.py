"""paged_prefill_attention: a lane's n_new queries at q_start.. attend
causally to its pages (decode lanes ride along with n_new = 1). Needed per
lane: the architecture's operations per key over each query's keys, and one
read of the lane's cache and page scales up to q_start + n_new, with the
queries read and the outputs written. One call per attention layer in each
mixed step."""
from __future__ import annotations

import math

from bench.counts import context_keys


def call(arch, model: dict, kv_bits: int, page: int, lanes):
    per_key = arch.attention_ops_per_key(model)
    ops = sum(per_key * context_keys(q0, n) for q0, n in lanes)
    nbytes = sum((q0 + n) * arch.kv_bytes_per_token(model, kv_bits)
                 + math.ceil((q0 + n) / page)
                 * arch.scale_bytes_per_page(model, kv_bits)
                 + n * arch.query_bytes(model) for q0, n in lanes)
    return ops, nbytes


def calls(arch, model: dict, quant: dict, step):
    if step.kind != "mixed":
        return []
    one = call(arch, model, quant["kv_bits"], quant["page_size"], step.lanes)
    return [one] * arch.attention_layers(model)
