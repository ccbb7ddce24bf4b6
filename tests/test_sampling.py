"""Unit tests for serving/sampling.py: the temperature / top-p paths and
the rejection-sampling acceptance rule speculative decoding builds on, and
the engine's own choice of sampler.

The distributional checks drive one jitted call with a large batch of
identical rows (speculative_accept draws independent uniforms per batch
element from a single key), so empirical frequencies converge at 1/sqrt(B)
and the tolerances stay loose enough for CI determinism across platforms.
"""
import numpy as np
import jax
import jax.numpy as jnp

from conftest import make_engine
from repro.serving import sampling

VOCAB = 8


def _logits(rows, seed=0, scale=2.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(rows, VOCAB)) * scale, jnp.float32)


# -- basic samplers ----------------------------------------------------------

def test_greedy_is_argmax():
    lg = _logits(16)
    got = np.asarray(sampling.greedy(lg))
    np.testing.assert_array_equal(got, np.argmax(np.asarray(lg), axis=-1))


def test_temperature_low_temp_is_greedy():
    lg = _logits(32)
    got = np.asarray(sampling.temperature(lg, jax.random.PRNGKey(0),
                                          temp=1e-4))
    np.testing.assert_array_equal(got, np.argmax(np.asarray(lg), axis=-1))


def test_temperature_matches_softmax_frequencies():
    """Sampled frequencies track softmax(logits / temp) per temperature."""
    n = 20_000
    row = _logits(1, seed=3)
    lg = jnp.broadcast_to(row, (n, VOCAB))
    for temp in (0.5, 1.0, 2.0):
        want = np.asarray(jax.nn.softmax(row[0] / temp))
        got = np.asarray(sampling.temperature(lg, jax.random.PRNGKey(1),
                                              temp=temp))
        freq = np.bincount(got, minlength=VOCAB) / n
        np.testing.assert_allclose(freq, want, atol=0.015)


# -- nucleus filtering -------------------------------------------------------

def test_filter_top_p_keeps_smallest_covering_set():
    probs = np.array([0.5, 0.3, 0.15, 0.05], np.float32)
    lg = jnp.asarray(np.log(probs))[None, :]
    # p strictly between the cumulative masses (0.5 < 0.75 < 0.8) so f32
    # rounding of the cumsum can't flip the boundary token either way
    out = np.asarray(sampling.filter_top_p(lg, p=0.75))[0]
    # {0.5, 0.3} is the smallest covering set; the tail drops to NEG_INF
    assert np.isfinite(out[0]) and np.isfinite(out[1])
    assert out[2] <= sampling.NEG_INF and out[3] <= sampling.NEG_INF


def test_filter_top_p_identity_at_one():
    lg = _logits(4)
    np.testing.assert_array_equal(np.asarray(sampling.filter_top_p(lg, 1.0)),
                                  np.asarray(lg))


def test_filter_top_p_keeps_threshold_ties():
    probs = np.full(4, 0.25, np.float32)
    lg = jnp.asarray(np.log(probs))[None, :]
    out = np.asarray(sampling.filter_top_p(lg, p=0.5))[0]
    # every token ties at the nucleus boundary: all stay
    assert np.isfinite(out).all()


def test_top_p_never_samples_filtered_tokens():
    probs = np.array([0.6, 0.25, 0.1, 0.05], np.float32)
    lg = jnp.broadcast_to(jnp.asarray(np.log(probs)), (4096, 4))
    got = np.asarray(sampling.top_p(lg, jax.random.PRNGKey(2), p=0.7,
                                    temp=1.0))
    assert set(np.unique(got)) <= {0, 1}


# -- speculative acceptance --------------------------------------------------

def _window(b, c, seed=0):
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=(b, c, VOCAB)) * 2, jnp.float32)
    draft = jnp.asarray(rng.integers(0, VOCAB, size=(b, c)), jnp.int32)
    return logits, draft


def test_speculative_accept_greedy_matches_reference_walk():
    b, c = 64, 6
    logits, draft = _window(b, c, seed=5)
    n_new = jnp.asarray(np.random.default_rng(6).integers(0, c + 1, b),
                        jnp.int32)
    emit, acc = sampling.speculative_accept(
        logits, draft, n_new, jax.random.PRNGKey(0), mode="greedy")
    emit, acc = np.asarray(emit), np.asarray(acc)
    g = np.argmax(np.asarray(logits), axis=-1)
    d = np.asarray(draft)
    for i in range(b):
        a = 0
        while a + 1 < int(n_new[i]) and g[i, a] == d[i, a + 1]:
            a += 1
        assert acc[i] == a
        # accepted drafts echo, then the bonus token is the argmax after
        # the last accepted position
        for j in range(a):
            assert emit[i, j] == d[i, j + 1]
        if n_new[i] > 0:
            assert emit[i, a] == g[i, a]


def test_speculative_accept_idle_and_undrafted_lanes():
    b, c = 8, 5
    logits, draft = _window(b, c, seed=7)
    n_new = jnp.asarray([0, 1] * 4, jnp.int32)
    emit, acc = sampling.speculative_accept(
        logits, draft, n_new, jax.random.PRNGKey(0), mode="greedy")
    assert (np.asarray(acc) == 0).all()          # nothing to accept
    g = np.argmax(np.asarray(logits), axis=-1)
    # n_new == 1 lanes reduce to a vanilla decode step on position 0
    np.testing.assert_array_equal(np.asarray(emit)[1::2, 0], g[1::2, 0])


def test_speculative_accept_rate_is_draft_probability():
    """A deterministic proposal d is accepted with probability p(d)."""
    n = 40_000
    row = _logits(1, seed=11)[0]
    p = np.asarray(jax.nn.softmax(row))
    d = int(np.argsort(p)[-2])                  # a likely-but-not-top token
    logits = jnp.broadcast_to(row, (n, 2, VOCAB))
    draft = jnp.full((n, 2), d, jnp.int32)
    n_new = jnp.full((n,), 2, jnp.int32)
    _, acc = sampling.speculative_accept(
        logits, draft, n_new, jax.random.PRNGKey(3), mode="temperature",
        temp=1.0)
    assert abs(float(np.mean(np.asarray(acc))) - p[d]) < 0.01


def test_speculative_accept_preserves_target_distribution():
    """The first emitted token is distributed as softmax(logits / temp)
    regardless of what the drafter proposed (the lossless-ness guarantee
    of rejection sampling: accept + residual-resample == target)."""
    n = 60_000
    row = _logits(1, seed=13)[0]
    for d in (int(np.argmax(np.asarray(row))), 0):
        logits = jnp.broadcast_to(row, (n, 2, VOCAB))
        draft = jnp.full((n, 2), d, jnp.int32)
        n_new = jnp.full((n,), 2, jnp.int32)
        emit, _ = sampling.speculative_accept(
            logits, draft, n_new, jax.random.PRNGKey(d + 1),
            mode="temperature", temp=0.9)
        freq = np.bincount(np.asarray(emit)[:, 0], minlength=VOCAB) / n
        want = np.asarray(jax.nn.softmax(row / 0.9))
        np.testing.assert_allclose(freq, want, atol=0.015)


# -- the engine's sampler ----------------------------------------------------

def test_engine_samples_with_its_sampler(cfg_params, kv_bits):
    """sampler="temperature" reaches the chunked and decode steps: at a
    high temperature the engine's tokens leave the greedy ones, and the
    same seed repeats them exactly. Greedy is keyless: its tokens do not
    depend on the seed."""
    cfg, params = cfg_params
    prompts = [list(range(1, 20)), [9] * 11, [3, 1, 4, 1, 5]]

    def serve(**kw):
        return make_engine(params, cfg, kv_bits=kv_bits, **kw).run(
            prompts, max_new=8).tokens

    greedy = serve()
    assert serve(seed=1) == greedy
    hot = serve(sampler="temperature", temperature=5.0, seed=3)
    assert hot != greedy
    assert serve(sampler="temperature", temperature=5.0, seed=3) == hot
    assert all(0 <= t < cfg.vocab for toks in hot for t in toks)
