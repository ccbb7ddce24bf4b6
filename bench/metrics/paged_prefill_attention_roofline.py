"""Share of paged_prefill_attention's device time in the traced slice that its
calls in the traced steps need at the v5e peaks (bf16 operations, HBM
bandwidth; counts from bench/counts/paged_prefill_attention.py)."""
from bench.counts import roofline_share


def read(ctx):
    return roofline_share(ctx, "paged_prefill_attention", "bf16_flops")
