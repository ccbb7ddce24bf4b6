"""The dense GQA architecture (`bench/arch/dense_gqa/`) gives, bit for bit,
what the harness gave before its layer moved there, at the toy size of
test_bench_harness.py: the seeded weights of two layers, the embedding and
the head (as sha256 of their bytes), the reference's logit summary of a
fixed sequence at W8A8 with int8 pages and at bf16, and every count on a
hand-built decode step and mixed step. `data/dense_gqa_pins.json` holds
the values, recorded from the harness before the move."""
import hashlib
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import counts, reference, weights  # noqa: E402
from bench.counts import (int8_matmul, paged_decode_attention,  # noqa: E402
                          paged_prefill_attention, step)
from bench.run import load_arch  # noqa: E402

with open(os.path.join(ROOT, "bench", "tests", "data",
                       "dense_gqa_pins.json")) as _f:
    PINS = json.load(_f)
ARCH = load_arch(ROOT, {"arch": "dense_gqa"})
MODEL = {"hidden_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 32, "intermediate_size": 256, "vocab_size": 512,
         "tie_word_embeddings": False, "rope_theta": 10000.0,
         "rms_norm_eps": 1e-05}
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bw": 819e9}
KERNELS = {"int8_matmul": (int8_matmul, "int8_ops"),
           "paged_decode_attention": (paged_decode_attention, "bf16_flops"),
           "paged_prefill_attention": (paged_prefill_attention,
                                       "bf16_flops")}


def _sha(a) -> str:
    a = np.asarray(a)
    return f"{a.dtype}{list(a.shape)}:" + hashlib.sha256(
        a.tobytes()).hexdigest()


def _steps():
    return {kind: SimpleNamespace(kind=kind, lanes=[tuple(x) for x in lanes],
                                  emitted=emitted)
            for kind, (lanes, emitted) in PINS["steps"].items()}


@pytest.mark.parametrize("name", sorted(PINS["weights"]))
def test_weights_are_bit_for_bit(name):
    key = weights.base_key(PINS["seed"])
    if name.startswith("layer"):
        i, leaf = name[len("layer"):].split(".")
        got = ARCH.weights.layer(key, int(i), MODEL)[leaf]
    else:
        got = getattr(weights, name)(key, MODEL)
    assert _sha(got) == PINS["weights"][name]


@pytest.mark.parametrize("precision", sorted(PINS["reference"]))
def test_reference_summary_is_bit_for_bit(precision):
    got = reference.logits_summary(
        ARCH, PINS["seed"], MODEL, PINS["precisions"][precision], 16,
        reference.padded_len(256), PINS["prompt"], PINS["served"],
        PINS["judge"])
    want = PINS["reference"][precision]
    assert set(got) == set(want)
    for k, v in want.items():
        dtype = np.int32 if k == "argmax" else np.float32
        np.testing.assert_array_equal(got[k], np.asarray(v, dtype))
        assert got[k].dtype == dtype


def test_shape_counts_are_bit_for_bit():
    c = PINS["counts"]
    sites = [tuple(x) for x in c["gemm_sites"]]
    assert ARCH.gemms(MODEL, 7) == [(7, k, n) for k, n in sites] * \
        MODEL["num_hidden_layers"]
    for kv in (16, 8, 4):
        assert ARCH.kv_bytes_per_token(MODEL, kv) == \
            c[f"kv_bytes_per_token.{kv}"]
        assert ARCH.scale_bytes_per_page(MODEL, kv) == \
            c[f"scale_bytes_per_page.{kv}"]


@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("kind", ["decode", "mixed"])
@pytest.mark.parametrize("weight_bits", [8, 4])
@pytest.mark.parametrize("kv_bits", [16, 8, 4])
def test_kernel_counts_are_bit_for_bit(kernel, kind, weight_bits, kv_bits):
    mod, rate = KERNELS[kernel]
    quant = {"weight_bits": weight_bits, "weight_group": 0, "act_bits": 8,
             "kv_bits": kv_bits, "page_size": 16}
    tag = f"w{weight_bits}.kv{kv_bits}.{kind}"
    calls = mod.calls(ARCH, MODEL, quant, _steps()[kind])
    assert [list(x) for x in calls] == PINS["counts"][f"{kernel}.calls.{tag}"]
    assert counts.least_time(calls, PEAKS[rate], PEAKS["hbm_bw"]) == \
        PINS["counts"][f"{kernel}.least_time.{tag}"]


@pytest.mark.parametrize("kind", ["decode", "mixed"])
def test_step_work_is_bit_for_bit(kind):
    s = _steps()[kind]
    assert list(step.work(ARCH, MODEL, s)) == \
        PINS["counts"][f"step.work.{kind}"]
    assert step.least_time(ARCH, MODEL, s, PEAKS) == \
        PINS["counts"][f"step.least_time.{kind}"]
