"""The serving engine's own records (serving/telemetry.py): spans of each
step's phases, request times and counts, step records, their bounds, one
recorder per engine, profiler annotations, and the named scopes of the
step programs."""
import dataclasses
import gc
import glob
import os
import re

import jax
import pytest

from conftest import make_engine
from repro.serving import cot, telemetry

PROMPTS = [list(range(1, 20)), [9] * 17, [3, 1, 4, 1, 5, 9, 2, 6]]
PHASES = {"serve.admit", "serve.plan", "serve.draft", "serve.inputs",
          "serve.launch", "serve.wait", "serve.commit"}


def _children(rec):
    """serve.step spans, each with its child spans in start order."""
    steps = {s.sid: (s, []) for s in rec.spans if s.name == "serve.step"}
    for s in rec.spans:
        if s.parent in steps:
            steps[s.parent][1].append(s)
    return [(p, sorted(c, key=lambda s: s.t0)) for p, c in steps.values()]


@pytest.mark.parametrize("spec", [0, 3])
def test_phases_nest_in_their_step_without_overlap(cfg_params, kv_bits,
                                                   spec):
    cfg, params = cfg_params
    eng = make_engine(params, cfg, kv_bits=kv_bits, spec_decode=spec)
    eng.run(PROMPTS, mode="no_think", max_new=12)
    rec = eng.telemetry
    steps = _children(rec)
    assert len(steps) == len(rec.steps) == rec.n_steps > 0
    for parent, kids in steps:
        assert {k.name for k in kids} <= PHASES
        assert all(k.step == parent.step for k in kids)
        assert all(parent.t0 <= k.t0 <= k.t1 <= parent.t1 for k in kids)
        assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))
        assert sum(k.t1 - k.t0 for k in kids) <= parent.t1 - parent.t0
    kinds = {r.kind for r in rec.steps}
    assert {"mixed", "decode"} <= kinds <= {"mixed", "decode", "verify",
                                            "idle"}
    assert rec.steps[-1].active == rec.steps[-1].waiting == 0
    for r in rec.steps:
        if r.kind == "mixed":      # the packed row axis that held its tokens
            assert r.token_slots == min(t for t in eng.row_sizes
                                        if t >= r.tokens)
        else:
            per_slot = eng.spec_tokens if r.kind == "verify" else 1
            assert r.token_slots == eng.sched.n_slots * per_slot
        assert r.tokens == sum(n for _, _, n in r.lanes) <= r.token_slots
    if not spec:      # a decode lane feeds one token and gets one back
        assert sum(r.tokens for r in rec.steps) == \
            eng.prefill_tokens + eng.decode_tokens


def test_request_times_are_ordered_and_outputs_whole(cfg_params, kv_bits):
    cfg, params = cfg_params
    eng = make_engine(params, cfg, kv_bits=kv_bits)
    res = eng.run(PROMPTS, mode="no_think", max_new=12)
    n_out = cot.budget_for("no_think", 0, 12)
    for rid, toks in enumerate(res.tokens):
        v = eng.request(rid)
        assert v.t_submit <= v.t_admit <= v.t_first <= v.t_done
        assert len(v.out) == n_out and list(v.out) == toks
        assert list(v.prompt) == eng._requests[rid].prompt
        assert (v.n_admits, v.n_preempted) == (1, 0)
        chunks = -(-len(v.prompt) // eng.chunk_tokens)
        assert v.prefill_steps == chunks
        emitting = [r for r in eng.telemetry.steps if rid in r.emitted]
        assert len(emitting) == n_out
    with pytest.raises(dataclasses.FrozenInstanceError):
        v.out = ()


def test_step_records_count_the_pages_walked(cfg_params, kv_bits):
    """A kernel step's `kv_pages_walked` is ceil(kv_len / page) summed over
    the program's lanes, an idle lane walking its one scratch page: less
    than the lanes x page-table width a walk of every column takes."""
    cfg, params = cfg_params
    eng = make_engine(params, cfg, kv_bits=kv_bits)
    eng.run(PROMPTS, mode="no_think", max_new=12)
    page, table = eng.page_size, eng.sched.page_table
    lanes, width = table.shape
    kernel_steps = [r for r in eng.telemetry.steps
                    if r.kind in ("mixed", "decode")]
    assert kernel_steps
    for r in eng.telemetry.steps:
        if r.kind not in ("mixed", "decode"):
            assert r.kv_pages_walked == 0
            continue
        want = sum(-(-(q0 + n) // page) for _, q0, n in r.lanes)
        assert r.kv_pages_walked == want + lanes - len(r.lanes)
        assert r.kv_pages_walked < lanes * width


def test_a_tight_pool_counts_preemptions_and_keeps_the_first_admit(
        cfg_params, kv_bits):
    cfg, params = cfg_params
    eng = make_engine(params, cfg, kv_bits=kv_bits, n_pages=7)
    admit, admits = eng.sched.admit, {}

    def watch(*a, **kw):            # the clock around every admission
        t0 = telemetry.clock()
        got = admit(*a, **kw)
        for _, req in got:
            admits.setdefault(req.rid, []).append((t0, telemetry.clock()))
        return got

    eng.sched.admit = watch
    res = eng.run(PROMPTS, mode="no_think", max_new=8)
    assert res.evictions > 0
    views = [eng.request(rid) for rid in range(len(PROMPTS))]
    assert sum(v.n_preempted for v in views) == res.evictions
    for v in views:
        assert v.n_admits == v.n_preempted + 1 == len(admits[v.rid])
        first = admits[v.rid][0]
        assert first[0] <= v.t_admit <= first[1]
        assert v.t_submit <= v.t_admit <= v.t_first <= v.t_done
    hit = next(v for v in views if v.n_preempted)
    assert hit.prefill_steps >= -(-len(hit.prompt) // eng.chunk_tokens)


def test_the_rings_stay_bounded(cfg_params, kv_bits, monkeypatch):
    monkeypatch.setattr(telemetry, "STEP_RING", 5)
    monkeypatch.setattr(telemetry, "SPAN_RING", 9)
    monkeypatch.setattr(telemetry, "REQUEST_RING", 2)
    cfg, params = cfg_params
    eng = make_engine(params, cfg, kv_bits=kv_bits)
    eng.run(PROMPTS + [[7] * 5], mode="no_think", max_new=12)
    rec = eng.telemetry
    assert rec.n_steps > 5
    assert len(rec.steps) == 5 and len(rec.spans) == 9
    assert [r.step for r in rec.steps] == list(range(rec.n_steps - 5,
                                                     rec.n_steps))
    assert len(rec.requests()) == 2
    with pytest.raises(KeyError):
        eng.request(0)
    assert len(eng._requests) == 4        # the engine's own map is whole


def test_two_engines_keep_their_records_apart(cfg_params, kv_bits):
    cfg, params = cfg_params
    a = make_engine(params, cfg, kv_bits=kv_bits)
    b = make_engine(params, cfg, kv_bits=kv_bits)
    assert telemetry.current() is b.telemetry
    a.run(PROMPTS[:1], mode="no_think", max_new=4)
    b.run(PROMPTS[1:], mode="no_think", max_new=4)
    assert [list(v.prompt) for v in a.telemetry.requests()] == \
        [a._requests[0].prompt]
    assert [list(v.prompt) for v in b.telemetry.requests()] == \
        [b._requests[0].prompt, b._requests[1].prompt]
    for e in (a, b):
        assert e.telemetry.n_steps == e.steps_run + e.mixed_steps
        assert len(e.telemetry.steps) == e.telemetry.n_steps
    del b, e
    gc.collect()
    assert telemetry.current() is a.telemetry


def test_a_profiler_trace_holds_the_serve_spans(cfg_params, tmp_path):
    cfg, params = cfg_params
    eng = make_engine(params, cfg, kv_bits=8)
    eng.run(PROMPTS[:1], mode="no_think", max_new=3)    # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(PROMPTS[1:], mode="no_think", max_new=3)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in data.planes if plane.name.startswith(
        "/host:") for line in plane.lines for ev in line.events}
    assert {"serve.step", "serve.admit", "serve.plan", "serve.inputs",
            "serve.launch", "serve.wait", "serve.commit"} <= names


def test_the_step_programs_carry_their_names_and_scopes(cfg_params,
                                                        kv_bits):
    cfg, params = cfg_params
    eng = make_engine(params, cfg, kv_bits=kv_bits)
    lowered = eng.lower_steps()
    programs = [("decode", lowered["decode"])] + [
        ("mixed", low) for low in lowered["mixed"].values()]
    for name, low in programs:
        text = low.as_text(debug_info=True)
        assert f"jit(serve_{name})" in text
        for scope in ("embed", "attention/kv_write",
                      "attention/paged_attention", "mlp", "lm_head"):
            assert re.search(rf'loc\("([^"]*/)?{scope}/', text), scope


def test_summary_splits_ttft(cfg_params):
    cfg, params = cfg_params
    eng = make_engine(params, cfg, kv_bits=8)
    assert eng.telemetry.summary()["ttft_p50_ms"] is None
    eng.run(PROMPTS, mode="no_think", max_new=6)
    s = eng.telemetry.summary()
    assert s["requests"] == 3 and s["steps"] == eng.telemetry.n_steps
    assert 0 <= s["admit_wait_p50_ms"] <= s["ttft_p50_ms"]
    assert 0 < s["prefill_p50_ms"] <= s["ttft_p50_ms"]
    assert s["host_ms_per_step"] > 0


@pytest.fixture(scope="module")
def ultra():
    from repro.configs import get_arch, reduced
    from repro.models import transformer
    cfg = reduced(get_arch("pangu_ultra_moe"))
    return cfg, transformer.init_params(jax.random.PRNGKey(0), cfg)


def test_latent_and_expert_scopes_name_their_parts(ultra):
    cfg, params = ultra
    eng = make_engine(params, cfg, kv_bits=8)
    lowered = eng.lower_steps()
    for low in [lowered["decode"], *lowered["mixed"].values()]:
        text = low.as_text(debug_info=True)
        for scope in ("attention/mla_absorb", "attention/kv_write",
                      "attention/paged_attention", "mlp/router",
                      "mlp/experts", "mlp/shared_expert"):
            assert re.search(rf'loc\("([^"]*/)?{scope}/', text), scope


def test_expert_counters_come_back_with_the_tokens(ultra, monkeypatch):
    """Each working step records the rows each held expert took in each
    layer of experts, and the pairs routed to experts not held: read back
    in the one transfer that brings the sampled tokens."""
    from repro.serving import engine as engine_mod
    cfg, params = ultra
    eng = make_engine(params, cfg, kv_bits=8)
    reads = []
    real = engine_mod.jax.device_get
    monkeypatch.setattr(engine_mod.jax, "device_get",
                        lambda x: reads.append(1) or real(x))
    eng.run(PROMPTS, mode="no_think", max_new=12)
    work = [r for r in eng.telemetry.steps if r.kind != "idle"]
    assert len(reads) == len(work)
    for r in work:
        assert len(r.expert_rows) == len(r.expert_absent) == cfg.n_groups
        assert all(len(rows) == cfg.moe.n_held for rows in r.expert_rows)
        assert sum(map(sum, r.expert_rows)) + sum(r.expert_absent) == \
            r.tokens * cfg.moe.top_k * cfg.n_groups


def test_a_dense_model_records_no_expert_counters(cfg_params):
    cfg, params = cfg_params
    eng = make_engine(params, cfg, kv_bits=8)
    eng.run(PROMPTS[:1], mode="no_think", max_new=3)
    assert all(r.expert_rows is None and r.expert_absent is None
               for r in eng.telemetry.steps)
