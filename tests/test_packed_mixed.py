"""The mixed step's packed row axis: the sizes it takes (from the engine's
geometry alone), the layout `transformer.pack_rows` gives the real tokens,
and an engine that picks the smallest size holding each step's tokens,
compiles every size when it is built and nothing while it serves."""
import jax
import numpy as np
import pytest

from conftest import make_engine
from repro.models import transformer
from repro.serving.engine import mixed_row_sizes


@pytest.mark.parametrize("geometry, sizes", [
    # bench/configs/pangu-1b-w8a8.json: 128-token chunks, 16 lanes
    ((128, 16, 2048), [256, 768, 2048]),
    # bench/configs/pangu-ultra-moe-w8a8.json: 128-token chunks, 64 lanes
    ((128, 64, 8192), [256, 768, 2304, 8192]),
    # the engine tests' default: one chunk beside every lane's token
    ((16, 3, 19), [19]),
    # a budget below a chunk beside the other lanes' tokens
    ((16, 3, 8), [18]),
    # sizes that round to the same multiple of 256 appear once
    ((16, 24, 384), [256, 384]),
])
def test_row_sizes_come_from_the_geometry(geometry, sizes):
    assert mixed_row_sizes(*geometry) == sizes


def test_pack_rows_lays_prompts_then_decode_lanes():
    """Rows in the order the lanes are given, each lane's tokens in order;
    padding rows after them (token 0, position 0, lane B); the grid names
    each real slot's row and T for the rest."""
    tokens = np.array([[7, 0, 0, 0], [1, 2, 3, 0], [0, 0, 0, 0],
                       [4, 5, 0, 0]], np.int32)
    q_start = np.array([9, 0, 0, 32], np.int32)
    n_new = np.array([1, 3, 0, 2], np.int32)
    toks, pos, lane, grid = transformer.pack_rows(tokens, q_start, n_new, 8,
                                                  [3, 1, 0])
    np.testing.assert_array_equal(toks, [4, 5, 1, 2, 3, 7, 0, 0])
    np.testing.assert_array_equal(pos, [32, 33, 0, 1, 2, 9, 0, 0])
    np.testing.assert_array_equal(lane, [3, 3, 1, 1, 1, 0, 4, 4])
    np.testing.assert_array_equal(grid, [[5, 8, 8, 8], [2, 3, 4, 8],
                                         [8, 8, 8, 8], [0, 1, 8, 8]])
    with pytest.raises(AssertionError):
        transformer.pack_rows(tokens, q_start, n_new, 5)


@pytest.fixture(scope="module")
def wide(cfg_params):
    """24 lanes of 16-token chunks under a budget of 384 tokens: row
    sizes 256 and 384."""
    cfg, params = cfg_params
    return make_engine(params, cfg, kv_bits=8, max_batch=24,
                       max_seq_len=48, token_budget=384)


def test_each_step_runs_the_smallest_size_that_holds_it(wide):
    """Every mixed step ran the program of the smallest row size that
    holds its real tokens, never more than the token budget, and its step
    record says so; both sizes ran. Nothing compiles after the engine is
    built, though the first request runs only the smaller size."""
    eng = wide
    assert eng.row_sizes == [256, 384]
    built = eng.compile_counts()
    assert built == {"mixed": 2, "decode": 1, "verify": 0}
    ran = []
    eng._mixed_progs = {
        t: (lambda t, f: lambda *a: ran.append(t) or f(*a))(t, f)
        for t, f in eng._mixed_progs.items()}
    eng.run([[5, 6, 7]], mode="no_think", max_new=2)  # the sampler compiles
    compiles = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 200, int(n)).tolist()
                   for n in rng.integers(9, 30, 40)]
        eng.run(prompts, mode="no_think", max_new=4)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    assert eng.compile_counts() == built
    mixed = [r for r in eng.telemetry.steps if r.kind == "mixed"]
    assert [r.token_slots for r in mixed] == ran
    for r in mixed:
        assert r.token_slots == min(t for t in eng.row_sizes
                                    if t >= r.tokens)
        assert r.tokens <= r.token_slots <= eng.token_budget
    assert set(ran) == {256, 384}
