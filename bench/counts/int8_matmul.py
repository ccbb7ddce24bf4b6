"""int8_matmul: int8 activations (M, K) x int8 weights (K, N), int32
accumulation, f32 output with per-token and per-channel scales. One call per
quantized linear per layer, with M = the step's real tokens."""
from __future__ import annotations

from bench.counts import gemm_sites


def call(m_rows: int, k: int, n: int):
    ops = 2 * m_rows * k * n
    nbytes = m_rows * k + k * n + 4 * m_rows + 4 * n + 4 * m_rows * n
    return ops, nbytes


def calls(model: dict, quant: dict, step):
    if quant["weight_bits"] != 8:
        return []
    rows = sum(n for _, n in step.lanes)
    return [call(rows, k, n) for k, n in gemm_sites(model)] * \
        model["num_hidden_layers"]
