"""int8_matmul: int8 activations (M, K) x int8 weights (K, N), int32
accumulation, f32 output with per-token and per-channel scales. One call per
quantized linear per layer (the architecture's `gemms`), with M = the rows
of the step's real tokens it gets."""
from __future__ import annotations


def call(m_rows: int, k: int, n: int):
    ops = 2 * m_rows * k * n
    nbytes = m_rows * k + k * n + 4 * m_rows + 4 * n + 4 * m_rows * n
    return ops, nbytes


def calls(arch, model: dict, quant: dict, step):
    if quant["weight_bits"] != 8:
        return []
    rows = sum(n for _, n in step.lanes)
    return [call(r, k, n) for r, k, n in arch.gemms(model, rows)]
