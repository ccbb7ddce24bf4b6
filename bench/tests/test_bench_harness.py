"""CPU tests of the harness as a whole, on a toy model: it finds a
configuration, a traffic mix and a metric by name in files it has never
seen; a run of the timed path is correct; the same run with a token
altered where the engine produces it, or with the control (the reference at
a lower precision) in the program's place, is not; and the command refuses
to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import adapter, check, drive, traffic  # noqa: E402
from bench import run as bench_run  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bw": 819e9}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
TOY_MODEL = {"hidden_size": 128, "num_hidden_layers": 2,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 32, "intermediate_size": 256, "vocab_size": 512,
             "tie_word_embeddings": False, "rope_theta": 10000.0,
             "rms_norm_eps": 1e-05}
TOY_SPEC = {
    "arch": "dense_gqa",
    "model": TOY_MODEL,
    "quant": {"weights": "int8", "weight_bits": 8, "weight_group": 0,
              "act_bits": 8, "kv_bits": 8},
    "control": {"weight_bits": 4, "weight_group": 0, "act_bits": 4,
                "kv_bits": 4},
    "engine": {"page_size": 16, "max_batch": 4, "max_seq_len": 256,
               "n_pages": 65, "chunk_pages": 2, "token_budget": 128,
               "prefix_cache": True},
    "check": {"logit_gap_limit": 0.5},
}
BF16_SPEC = dict(
    TOY_SPEC,
    quant={"weights": "bf16", "weight_bits": 16, "weight_group": 0,
           "act_bits": 16, "kv_bits": 16},
    control={"weight_bits": 8, "weight_group": 0, "act_bits": 8,
             "kv_bits": 8})
TOY_MIX = {"loop": "open", "mode": "no_think", "rate_per_s": 6,
           "warm_s": 0.3,
           "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                             "min": 20, "max": 100},
           "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
           "schedule_seed": 1}
TOY_METRIC = '''"""Requests the toy run finished in all."""


def read(ctx):
    return float(sum(r.done is not None for r in ctx.record.requests))
'''


def _toy_root(root, spec):
    """A checkout-like directory that holds only data: a BENCHMARK.json
    naming a toy configuration, a toy traffic mix and a toy metric, whose
    files lie where the harness looks for them by name, beside a copy of
    the configuration's architecture."""
    for sub in ("configs", "traffic", "metrics"):
        (root / "bench" / sub).mkdir(parents=True)
    shutil.copytree(os.path.join(ROOT, "bench", "arch", spec["arch"]),
                    root / "bench" / "arch" / spec["arch"],
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(spec))
    (root / "bench" / "traffic" / "toy-mix.json").write_text(
        json.dumps(TOY_MIX))
    (root / "bench" / "metrics" / "toy_finished.py").write_text(TOY_METRIC)
    for name in ("setup_s", "itl_p95_ms", "ttft_p50_ms"):
        shutil.copy(os.path.join(ROOT, "bench", "metrics", f"{name}.py"),
                    root / "bench" / "metrics")
    bench = {
        "configs": [{"name": "toy", "source": "toy", "reduced": [],
                     "file": "bench/configs/toy.json", "why": "toy"}],
        "workloads": [{"name": "toy.mix", "config": "toy",
                       "traffic": "toy-mix", "chips": 1, "why": "toy"}],
        "end_to_end": [
            {"name": n, "unit": u, "better": "lower", "bound": 0.25,
             "source": "host_clock"}
            for n, u in (("ttft_p50_ms", "ms"), ("itl_p95_ms", "ms"),
                         ("setup_s", "s"))],
        "per_layer": [{"name": "toy_finished", "unit": "count",
                       "better": "higher", "source": "program_counter",
                       "layer": "toy", "moves": "ttft_p50_ms"}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return _toy_root(tmp_path_factory.mktemp("toy"), TOY_SPEC)


def _run(root, trace=False, seed=5, control=False):
    old = bench_run.DRAIN_S
    bench_run.DRAIN_S = 5.0
    try:
        return bench_run.run_cell(root, "toy.mix", seed, 1.0, trace,
                                  device=DEVICE, peaks=PEAKS,
                                  persistent_cache=False, control=control)
    finally:
        bench_run.DRAIN_S = old


def test_harness_finds_new_files_by_name_and_is_correct(toy_root):
    res = _run(toy_root)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    assert res["checks"]["logit_gap"]["value"] <= 0.5
    assert res["checks"]["wrong_lengths"]["value"] == 0
    assert res["device"] == dict(DEVICE, memory_peak_bytes=None)
    per_layer = _run(toy_root, trace=True)
    assert per_layer["correct"] is True
    assert per_layer["metrics"]["toy_finished"]["value"] >= 1
    assert list(per_layer)[-1] == "checks"


class _AlteredTokens(adapter.ContinuousBatchingEngine):
    """The engine with every sampled token moved by one id, where the
    tokens are produced."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        sample = self._sample
        self._sample = lambda lg: (sample(lg) + 1) % (self.cfg.vocab - 8)


def test_an_altered_token_makes_the_run_incorrect(toy_root, monkeypatch):
    monkeypatch.setattr(adapter, "ContinuousBatchingEngine", _AlteredTokens)
    res = _run(toy_root)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["max"]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_control_is_not_correct(seed):
    """The reference at the control precision (every int8 of W8A8 with
    int8 KV taken to int4), in the program's place, fails the limit that
    the program's own tokens pass."""
    arch = bench_run.load_arch(ROOT, TOY_SPEC)
    server = adapter.Server("toy", TOY_SPEC, seed, arch)
    mix = dict(TOY_MIX, loop="closed", sessions=4, staggered=True,
               mode="slow_think",
               output_tokens={"dist": "uniform", "min": 20, "max": 60})
    loop = drive.Loop(server, "slow_think", None)
    for req in traffic.closed_loop(mix, seed, 4, 512)[:4]:
        loop.submit(req, 0.0)
    while loop.busy():
        loop.step()
    done = [{"rid": r.rid, "n_out": r.n_out, "prompt": server.prompt(r.rid),
             "served": server.out(r.rid)} for r in loop.all]
    server.close()
    got = check.gaps(arch, seed, TOY_SPEC, done, control=True)
    limit = TOY_SPEC["check"]["logit_gap_limit"]
    assert got["logit_gap"] <= limit < got["control_gap"]


def test_the_control_in_the_programs_place_is_not_correct(toy_root):
    """A run in control mode judges the control's first choices by the same
    comparison and limit as the program's tokens, and so is not correct."""
    res = _run(toy_root, seed=7, control=True)
    checks = res["checks"]
    assert checks["logit_gap"]["value"] <= checks["logit_gap"]["max"]
    assert checks["control_gap"]["value"] > checks["control_gap"]["max"]
    assert res["correct"] is False


def test_a_bf16_configuration_is_correct(tmp_path):
    """16-bit weights, activations and pages: the reference's bfloat16
    path agrees with what the program served."""
    root = _toy_root(tmp_path, BF16_SPEC)
    res = _run(root)
    assert res["correct"] is True
    assert res["checks"]["tokens_compared"]["value"] > 0
    assert res["checks"]["logit_gap"]["value"] <= 0.5


def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "pangu-1b-w8a8.code-open", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
