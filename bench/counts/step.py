"""Useful model work of one engine step: the real tokens' linears (int8 MXU
operations, W8A8 and W4A8 alike), their attention over their real context
and the LM head of the lanes that got a token (bf16 operations). Padding
lanes, padding rows and logits that are thrown away are not useful work.
The shapes come from the architecture (`bench/arch/<arch>/`)."""
from __future__ import annotations

from bench.counts import context_keys


def work(arch, model: dict, step):
    """(int8 ops, bf16 ops) of the step."""
    tokens = sum(n for _, n in step.lanes)
    gemm = 2 * sum(r * k * n for r, k, n in arch.gemms(model, tokens))
    attn = (arch.attention_ops_per_key(model) * arch.attention_layers(model)
            * sum(context_keys(q0, n) for q0, n in step.lanes))
    head = 2 * model["hidden_size"] * model["vocab_size"] * step.emitted
    return gemm, attn + head


def least_time(arch, model: dict, step, peaks) -> float:
    """Seconds the step's useful work takes at the chip's peak rates."""
    int8_ops, bf16_ops = work(arch, model, step)
    return int8_ops / peaks["int8_ops"] + bf16_ops / peaks["bf16_flops"]
