"""Share of int8_matmul's device time in the traced slice that its calls in the
traced steps need at the v5e peaks (int8 operations, HBM bandwidth; counts
from bench/counts/int8_matmul.py)."""
from bench.counts import roofline_share


def read(ctx):
    return roofline_share(ctx, "int8_matmul", "int8_ops")
