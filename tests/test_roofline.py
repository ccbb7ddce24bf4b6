"""Roofline machinery tests: the loop-aware HLO walker must (a) match
XLA cost_analysis on loop-free modules, (b) multiply while bodies by trip
count, (c) count collectives inside loops with multipliers."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.roofline import analysis, hlo_cost, hw


def _compile(f, *specs, **jit_kw):
    return jax.jit(f, **jit_kw).lower(*specs).compile()


def test_walker_matches_cost_analysis_loop_free():
    def f(x, w1, w2):
        return jax.nn.relu(x @ w1) @ w2

    s = jax.ShapeDtypeStruct
    comp = _compile(f, s((128, 256), jnp.float32), s((256, 512), jnp.float32),
                    s((512, 64), jnp.float32))
    got = hlo_cost.analyze(comp.as_text())
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    expected_flops = 2 * 128 * 256 * 512 + 2 * 128 * 512 * 64
    assert got["flops"] == expected_flops
    # XLA adds elementwise flops; GEMMs dominate
    assert abs(ca["flops"] - got["flops"]) / got["flops"] < 0.02


def test_walker_multiplies_while_trip_count():
    def f(x, w):
        def body(c, _):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    s = jax.ShapeDtypeStruct
    comp = _compile(f, s((64, 64), jnp.float32), s((64, 64), jnp.float32))
    got = hlo_cost.analyze(comp.as_text())
    assert got["flops"] == 7 * 2 * 64 * 64 * 64
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    assert ca["flops"] < got["flops"]  # XLA undercounts the loop


def test_walker_nested_scan():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, ()
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, ()
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    s = jax.ShapeDtypeStruct
    comp = _compile(f, s((32, 32), jnp.float32), s((32, 32), jnp.float32))
    got = hlo_cost.analyze(comp.as_text())
    assert got["flops"] == 15 * 2 * 32 ** 3


def test_collectives_counted_with_loop_multiplier():
    import os
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (run under forced host device count)")


def test_roofline_terms_dominance():
    t = analysis.roofline_terms(hlo_flops_per_dev=1e12,
                                hlo_bytes_per_dev=1e9,
                                link_bytes_per_dev=1e6, n_chips=256)
    assert t["dominant"] == "compute_s"
    assert abs(t["compute_s"] - 1e12 / hw.peaks(hw.V5E).bf16_flops) < 1e-9
    t2 = analysis.roofline_terms(hlo_flops_per_dev=1e9,
                                 hlo_bytes_per_dev=1e12,
                                 link_bytes_per_dev=1e6, n_chips=256)
    assert t2["dominant"] == "memory_s"


def test_int8_split_peak():
    t = analysis.roofline_terms(hlo_flops_per_dev=2e12,
                                hlo_bytes_per_dev=1.0,
                                link_bytes_per_dev=0.0, n_chips=1,
                                int8_linear_flops_global=2e12)
    # all flops at int8 peak
    assert abs(t["compute_s"] - 2e12 / hw.peaks(hw.V5E).int8_ops) < 1e-9


def test_peaks_keyed_by_device_kind():
    pk = hw.peaks("TPU v5 lite")
    assert (pk.bf16_flops, pk.int8_ops, pk.hbm_bw, pk.ici_bw) == (
        197e12, 393e12, 819e9, 50e9)
    # a kind with no recorded peaks raises; it never borrows another's
    for kind in ("cpu", "TPU v4"):
        with pytest.raises(KeyError, match="no peaks recorded"):
            hw.peaks(kind)


def test_collective_ring_adjustments():
    c = hlo_cost.CollectiveUse("all-gather", 100, 4, 2)
    assert c.link_bytes == 100 * 3 * 2
    c = hlo_cost.CollectiveUse("all-reduce", 100, 4, 1)
    assert c.link_bytes == int(2 * 100 * 3 / 4)
    c = hlo_cost.CollectiveUse("collective-permute", 100, 4, 3)
    assert c.link_bytes == 300
