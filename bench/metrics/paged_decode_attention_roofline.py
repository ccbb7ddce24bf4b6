"""Share of paged_decode_attention's device time in the traced slice that its
calls in the traced steps need at the v5e peaks (bf16 operations, HBM
bandwidth; counts from bench/counts/paged_decode_attention.py)."""
from bench.counts import roofline_share


def read(ctx):
    return roofline_share(ctx, "paged_decode_attention", "bf16_flops")
