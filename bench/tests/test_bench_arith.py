"""CPU tests of the benchmark's yardstick: percentiles and rates, the
traffic generator, the operation and byte counts, the metric readers and
the reduction of a recorded trace."""
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import counts, stats, traffic  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.counts import (int8_matmul, paged_decode_attention,  # noqa: E402
                          paged_prefill_attention, step)
from bench.drive import Record, RequestRecord, StepRecord  # noqa: E402
from bench.run import Context, load_arch, load_reader  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bw": 819e9}
MODEL = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 4, "intermediate_size": 16,
         "vocab_size": 100}
QUANT = {"weight_bits": 8, "weight_group": 0, "act_bits": 8, "kv_bits": 8,
         "page_size": 4}
TRACE = os.path.join(ROOT, "bench", "tests", "data", "small_trace.json")
ARCH = load_arch(ROOT, {"arch": "dense_gqa"})


# -- percentiles and rates ----------------------------------------------------

@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
def test_percentile_matches_numpy_linear(q):
    xs = np.random.default_rng(0).exponential(size=37).tolist()
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 90) is None


def test_ttft_censors_requests_without_a_first_token():
    # due 0, 1, 2; the third never got a token before the stop at 5
    assert stats.ttfts([0.0, 1.0, 2.0], [0.5, 3.0, None], 5.0) == \
        [0.5, 2.0, 3.0]
    # a first token seen after the stop counts only the wait until the stop
    assert stats.ttfts([0.0], [9.0], 5.0) == [5.0]


def test_censored_ttfts_raise_the_tail():
    firsts = [0.1] * 9 + [None]
    assert stats.percentile(stats.ttfts([0.0] * 10, firsts, 30.0), 95) > 10


def test_gaps_and_rates_over_the_window():
    times = [0.5, 1.0, 1.5, 2.5, 4.0]
    assert stats.gaps_in(times, 1.0, 3.0) == [0.5, 0.5, 1.0]
    assert stats.count_in(times, 1.0, 3.0) == 3
    assert stats.rate(3, 2.0) == 1.5


def test_spread_is_quartile_distance_over_median():
    assert stats.spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(4 / 4)


# -- traffic ------------------------------------------------------------------

OPEN = {"loop": "open", "mode": "no_think", "rate_per_s": 2.0, "warm_s": 5,
        "prompt_tokens": {"dist": "lognormal", "median": 1400, "sigma": 0.6,
                          "min": 512, "max": 4096},
        "output_tokens": {"dist": "lognormal", "median": 160, "sigma": 0.6,
                          "min": 64, "max": 512},
        "schedule_seed": 12}
CLOSED = {"loop": "closed", "mode": "slow_think", "sessions": "max_batch",
          "prompt_tokens": {"dist": "uniform", "min": 256, "max": 1024},
          "output_tokens": {"dist": "lognormal", "median": 3584,
                            "sigma": 0.45, "min": 2048, "max": 8192},
          "staggered": True, "schedule_seed": 7}


def test_open_loop_is_determined_by_the_seed():
    a = traffic.open_loop(OPEN, 2**33 + 5, 30, 153376)
    b = traffic.open_loop(OPEN, 2**33 + 5, 30, 153376)
    assert [(r.due, r.prompt, r.n_out) for r in a] == \
        [(r.due, r.prompt, r.n_out) for r in b]


def test_open_loop_sizes_and_arrivals_do_not_depend_on_the_seed():
    a = traffic.open_loop(OPEN, 1, 30, 153376)
    b = traffic.open_loop(OPEN, 2, 30, 153376)
    assert [(r.due, len(r.prompt), r.n_out) for r in a] == \
        [(r.due, len(r.prompt), r.n_out) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_open_loop_lengths_rate_and_token_ids():
    reqs = traffic.open_loop(OPEN, 3, 30, 1000)
    assert len(reqs) == int(np.ceil(2.0 * 35)) + 1
    assert all(512 <= len(r.prompt) <= 4096 for r in reqs)
    assert all(64 <= r.n_out <= 512 for r in reqs)
    assert all(0 <= t < 1000 - traffic.RESERVED_TOP_IDS
               for r in reqs for t in r.prompt)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)
    assert (dues[-1] - dues[0]) / (len(dues) - 1) == pytest.approx(0.5,
                                                                   rel=0.05)
    med = np.median([len(r.prompt) for r in reqs])
    assert 1250 <= med <= 1550


def test_closed_loop_staggers_the_first_sessions():
    reqs = traffic.closed_loop(CLOSED, 9, 16, 153376)
    assert len(reqs) == 48
    first, later = reqs[:16], reqs[16:]
    assert all(r.done_before > 0 for r in first)
    assert all(r.done_before == 0 for r in later)
    for r in reqs:
        total = r.done_before + r.n_out
        assert 2048 <= total <= 8192
        assert 256 <= len(r.prompt) - r.done_before <= 1024
    shares = sorted(r.done_before / (r.done_before + r.n_out) for r in first)
    assert shares[0] < 0.1 and shares[-1] > 0.9
    again = traffic.closed_loop(CLOSED, 9, 16, 153376)
    assert [r.prompt for r in again] == [r.prompt for r in reqs]


def test_bursts_keep_the_mean_rate_and_arrive_together():
    bursty = dict(OPEN, arrivals={"process": "bursts", "size": 4})
    reqs = traffic.open_loop(bursty, 3, 30, 1000)
    assert len(reqs) == len(traffic.open_loop(OPEN, 3, 30, 1000))
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)
    groups = [dues[i:i + 4] for i in range(0, len(dues), 4)]
    assert all(len(set(g)) == 1 for g in groups)
    starts = [g[0] for g in groups]
    assert (starts[-1] - starts[0]) / (len(starts) - 1) == pytest.approx(
        4 / 2.0, rel=0.1)
    again = traffic.open_loop(bursty, 4, 30, 1000)
    assert [r.due for r in again] == dues


def test_unknown_arrival_process_is_an_error():
    with pytest.raises(ValueError):
        traffic.open_loop(dict(OPEN, arrivals={"process": "gamma"}), 3, 30,
                          1000)


def _requests(loop, **extra):
    if loop == "open":
        return traffic.open_loop(dict(OPEN, **extra), 5, 30, 1000)
    return traffic.closed_loop(dict(CLOSED, staggered=False, **extra), 5, 6,
                               1000)


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_shared_prefixes_by_group(loop):
    shared = {"groups": 3, "tokens": {"dist": "uniform", "min": 100,
                                      "max": 200}}
    reqs = _requests(loop, shared_prefix=shared)
    plain = _requests(loop)
    heads = set()
    for r, p in zip(reqs, plain):
        head = r.prompt[:len(r.prompt) - len(p.prompt)]
        assert 100 <= len(head) <= 200
        heads.add(tuple(head))
    assert len(heads) == 3
    assert [r.prompt for r in _requests(loop, shared_prefix=shared)] == \
        [r.prompt for r in reqs]


def test_lengths_are_quantiles_in_range():
    xs = traffic.lengths({"dist": "uniform", "min": 10, "max": 20}, 5)
    assert xs.tolist() == list(range(11, 20, 2))


# -- the traced slice -----------------------------------------------------------

def test_the_traced_slice_holds_both_step_programs(monkeypatch):
    from bench import drive
    calls = []
    monkeypatch.setattr(drive.jax.profiler, "start_trace",
                        lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(drive.jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    t = drive.Tracer("unused", start=10.0, seconds=3.0, last=40.0)
    t.observe("mixed")
    t.at_boundary(11.0)                 # after a mixed step: not yet
    assert not t.on
    t.observe("decode")
    t.at_boundary(11.5)                 # after a decode step: starts
    assert t.on
    for now in (12.0, 20.0):            # only decode steps so far
        t.observe("decode")
        t.at_boundary(now)
    assert t.on
    t.observe("mixed")
    t.at_boundary(21.0)
    assert (t.on, t.done, calls) == (False, True, ["start", "stop"])
    # only mixed steps: it starts `seconds` late and stops at `last`
    u = drive.Tracer("unused", start=10.0, seconds=3.0, last=40.0)
    for now in (11.0, 13.5, 30.0, 40.0):
        u.observe("mixed")
        u.at_boundary(now)
    assert u.done and calls[2:] == ["start", "stop"]


# -- the reference's linears --------------------------------------------------

def test_reference_linear_16_bit_is_a_bf16_matmul():
    import jax.numpy as jnp
    from bench import reference
    rng = np.random.default_rng(0)
    x = reference.bf(jnp.asarray(rng.normal(size=(3, 64)), jnp.float32))
    w = jnp.asarray(rng.normal(size=(64, 8)) * 100, jnp.float32)
    p16 = {"weight_bits": 16, "weight_group": 0, "act_bits": 16}
    want = np.asarray(x, np.float64) @ np.asarray(reference.bf(w), np.float64)
    np.testing.assert_allclose(reference.linear(x, w, p16), want, rtol=1e-5)
    with pytest.raises(ValueError):
        reference.linear(x, w, dict(p16, act_bits=8))


def test_padded_len_is_whole_query_blocks():
    from bench import reference
    assert reference.padded_len(4624) == 4864
    assert reference.padded_len(reference.Q_BLOCK) == reference.Q_BLOCK


# -- counts -------------------------------------------------------------------

def test_int8_matmul_counts_by_hand():
    # M=3, K=8, N=16: 2*3*8*16 ops; bytes 24 + 128 + 12 + 64 + 192
    assert int8_matmul.call(3, 8, 16) == (768, 420)


def test_decode_attention_counts_by_hand():
    # one lane of 5 keys, 4 q heads of 4, 2 kv heads, int8, pages of 4:
    # ops 4*4*4*5; K and V 5*2*2*4 bytes; 2 pages of 2 heads' 2 scales;
    # q in and out 2*2*4*4 bytes
    assert paged_decode_attention.call(ARCH, MODEL, 8, 4, [5]) == \
        (320, 80 + 32 + 64)


def test_prefill_attention_counts_by_hand():
    # 3 queries at 2..4 see 3 + 4 + 5 = 12 keys; context 5 tokens
    ops, nbytes = paged_prefill_attention.call(ARCH, MODEL, 4, 4, [(2, 3)])
    assert ops == 4 * 4 * 4 * 12
    assert nbytes == 5 * 2 * 2 * 4 * 0.5 + 2 * 16 + 3 * 64


def test_step_work_by_hand():
    s = SimpleNamespace(kind="mixed", lanes=[(0, 2), (5, 1)], emitted=1)
    int8_ops, bf16_ops = step.work(ARCH, MODEL, s)
    per_token = 8 * 32 + 16 * 8 + 8 * 32 + 16 * 8
    assert int8_ops == 2 * 3 * 2 * per_token
    assert bf16_ops == 4 * 4 * 4 * 2 * (3 + 6) + 2 * 8 * 100


# A cache that is not (nkv, hd) keys and values: one latent vector of 576
# (512 compressed + 64 rotary) per token and layer, one f32 scale per page,
# attended in absorbed form by 128 heads (q.k over 576, p.v over 512), in
# 5 layers.
LATENT = SimpleNamespace(
    attention_layers=lambda m: 5,
    gemms=lambda m, tokens: [(tokens, 7680, 1536)],
    attention_ops_per_key=lambda m: 2 * 128 * (576 + 512),
    query_bytes=lambda m: 2 * 128 * (576 + 512),
    kv_bytes_per_token=lambda m, kv_bits: 576 * kv_bits / 8,
    scale_bytes_per_page=lambda m, kv_bits: 0 if kv_bits == 16 else 4)


def test_latent_cache_counts_by_hand():
    # decode, 40 keys, int8, pages of 16: 278528 ops a key; 40 latents of
    # 576 bytes, 3 pages' scales, a 128 x 576 query in and 128 x 512 out
    assert paged_decode_attention.call(LATENT, {}, 8, 16, [40]) == \
        (11141120, 23040 + 12 + 278528)
    # 8 queries at 32..39 see 33 + ... + 40 = 292 keys; bf16 latents
    ops, nbytes = paged_prefill_attention.call(LATENT, {}, 16, 16, [(32, 8)])
    assert (ops, nbytes) == (278528 * 292, 40 * 1152 + 8 * 278528)
    dec = SimpleNamespace(kind="decode", lanes=[(39, 1)], emitted=1)
    assert paged_decode_attention.calls(LATENT, {}, QUANT, dec) == \
        [paged_decode_attention.call(LATENT, {}, 8, 4, [40])] * 5
    assert int8_matmul.calls(LATENT, {}, QUANT, dec) == \
        [int8_matmul.call(1, 7680, 1536)]
    model = {"hidden_size": 7680, "vocab_size": 19200}
    assert step.work(LATENT, model, dec) == (
        2 * 7680 * 1536, 278528 * 5 * 40 + 2 * 7680 * 19200)
    # the readers every cell reports read it too
    rec = Record((0.0, 10.0), steps=[StepRecord(1.0, 1.5, "decode",
                                                [(39, 1)], 1, True)])
    ctx = Context(rec, model, QUANT, {}, PEAKS, 1.0,
                  {"kernel_s": {"paged_decode_attention": 1.0},
                   "busy_s": 1.0, "window_s": 2.0}, LATENT)
    for name in ("step_ms.decode", "mfu.decode_step",
                 "paged_decode_attention_roofline", "idle_share"):
        assert load_reader(ROOT, name)(ctx) > 0


def test_calls_follow_the_step_kind_and_weight_bits():
    dec = SimpleNamespace(kind="decode", lanes=[(9, 1)], emitted=1)
    mix = SimpleNamespace(kind="mixed", lanes=[(0, 4)], emitted=0)
    assert len(paged_decode_attention.calls(ARCH, MODEL, QUANT, dec)) == 2
    assert paged_decode_attention.calls(ARCH, MODEL, QUANT, mix) == []
    assert len(paged_prefill_attention.calls(ARCH, MODEL, QUANT, mix)) == 2
    assert len(int8_matmul.calls(ARCH, MODEL, QUANT, mix)) == 8
    assert int8_matmul.calls(ARCH, MODEL, dict(QUANT, weight_bits=4),
                             mix) == []


def _ctx(steps, trace=None, requests=(), window=(0.0, 10.0)):
    rec = Record(window, stop=window[1], requests=list(requests),
                 steps=steps)
    return Context(rec, MODEL, QUANT, {"max_batch": 4}, PEAKS, 12.5, trace,
                   ARCH)


def test_roofline_share_is_100_when_the_kernel_takes_its_least_time():
    s = StepRecord(1.0, 2.0, "decode", [(99, 1), (9, 1)], 2, True)
    need = counts.least_time(
        paged_decode_attention.calls(ARCH, MODEL, QUANT, s),
        PEAKS["bf16_flops"], PEAKS["hbm_bw"])
    ctx = _ctx([s], {"kernel_s": {"paged_decode_attention": need}})
    read = load_reader(ROOT, "paged_decode_attention_roofline")
    assert read(ctx) == pytest.approx(100.0)
    ctx.trace["kernel_s"]["paged_decode_attention"] = 4 * need
    assert read(ctx) == pytest.approx(25.0)


def test_readers_return_none_where_nothing_is_read():
    s = StepRecord(1.0, 2.0, "decode", [(9, 1)], 1, False)
    for name in ("paged_decode_attention_roofline", "int8_matmul_roofline",
                 "idle_share"):
        assert load_reader(ROOT, name)(_ctx([s])) is None
    ctx = _ctx([s], {"kernel_s": {}})
    assert load_reader(ROOT, "paged_prefill_attention_roofline")(ctx) is None
    assert load_reader(ROOT, "step_ms.mixed")(ctx) is None


def test_host_readers():
    reqs = [RequestRecord(i, due=1.0 + i, submit=1.0 + i + 0.01 * i,
                          n_out=3, prompt_len=10, admit=2.0 + i,
                          tokens=[3.0 + i, 3.5 + i, 4.0 + i])
            for i in range(5)]
    steps = [StepRecord(1.0, 1.5, "decode", [(9, 1), (4, 1)], 2, False),
             StepRecord(2.0, 2.3, "mixed", [(0, 8)], 0, False)]
    ctx = _ctx(steps, requests=reqs)
    lag = load_reader(ROOT, "gen_lag_ms.p95")(ctx)
    assert lag == pytest.approx(1e3 * np.percentile([0.01 * i for i in
                                                      range(5)], 95))
    assert load_reader(ROOT, "queue_wait_ms.p90")(ctx) == pytest.approx(1e3)
    # a request held across the profiler's start is the tracer's, not the
    # generator's lag
    late = RequestRecord(9, due=6.0, submit=8.0, n_out=3, prompt_len=10,
                         admit=8.5, tokens=[9.0])
    held = _ctx(steps, requests=reqs + [late])
    assert load_reader(ROOT, "gen_lag_ms.p95")(held) > 1e3
    held.record.stalls = [(6.5, 7.9)]
    assert load_reader(ROOT, "gen_lag_ms.p95")(held) == pytest.approx(lag)
    assert load_reader(ROOT, "step_ms.decode")(ctx) == pytest.approx(500)
    assert load_reader(ROOT, "ttft_p50_ms")(ctx) == pytest.approx(2000)
    assert load_reader(ROOT, "itl_p95_ms")(ctx) == pytest.approx(500)
    assert load_reader(ROOT, "setup_s")(ctx) == 12.5
    mfu = load_reader(ROOT, "mfu.decode_step")(ctx)
    assert 0 < mfu < 100
    assert mfu == pytest.approx(100 * step.least_time(ARCH, MODEL, steps[0],
                                                      PEAKS) / 0.5)


# -- trace reduction ---------------------------------------------------------

def test_reduce_toy_trace_by_hand():
    ops = [["%fusion.1 = f32[] fusion()", 0, 100],
           ["%while.2 = (s32[]) while(%t)", 100, 400],
           ["%int8_matmul.7 = f32[] custom-call()", 150, 100],
           ["%int8_matmul.7 = f32[] custom-call()", 300, 50],
           ["%copy.3 = f32[] copy()", 700, 120]]
    host = [["engine.step", 0, 600], ["bench.observe", 600, 100],
            ["engine.step", 700, 300]]
    red = trace_mod.reduce({"devices": [ops], "host": host})
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(620e-9)
    assert red["kernel_s"]["int8_matmul"] == pytest.approx(150e-9)
    names = [n for n, _ in red["device_ops"]]
    assert "while.2" not in names and names[0] == "int8_matmul.7"
    assert red["idle_gaps"] == [["bench.observe", pytest.approx(200e-9)],
                                ["engine.step", pytest.approx(180e-9)]]


def test_reduce_recorded_trace():
    with open(TRACE) as f:
        recorded = json.load(f)
    red = trace_mod.reduce(recorded)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["kernel_s"]["paged_decode_attention"] > 0
    assert red["kernel_s"]["int8_matmul"] > 0
    assert len(red["device_ops"]) == trace_mod.TOP
    assert all(n in trace_mod.HOST_SPANS + ("host.other",)
               for n, _ in red["idle_gaps"])
    share = 1 - red["busy_s"] / red["window_s"]
    assert 0 <= share < 1


def test_reduce_without_steps_or_device_is_empty():
    assert trace_mod.reduce({"devices": [[]], "host": []}) == {}
