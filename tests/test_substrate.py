"""Substrate tests: data determinism/skip-ahead, optimizer, trainer
(learning + microbatch equivalence + compressed DP), serving engine + CoT,
checkpoint save/restore/elastic."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.configs import get_arch, reduced
from repro.data import DataConfig, SyntheticLM
from repro.models import transformer
from repro.optim import adamw
from repro.serving import ContinuousBatchingEngine, cot
from repro.train import trainer


def tiny_setup(arch="pangu_1b", seed=0):
    cfg = reduced(get_arch(arch))
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32, seed=seed))
    return cfg, data


# -- data ---------------------------------------------------------------------

def test_data_deterministic_and_skip_ahead():
    _, data = tiny_setup()
    b1 = data.batch(5, 4)
    b2 = data.batch(5, 4)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = data.batch(6, 4)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))
    # labels are next-token shifted stream
    assert b1["tokens"].shape == b1["labels"].shape == (4, 32)
    # host sharding decorrelates
    h0 = data.batch(5, 4, host_id=0, num_hosts=2)
    h1 = data.batch(5, 4, host_id=1, num_hosts=2)
    assert not np.array_equal(np.asarray(h0["tokens"]),
                              np.asarray(h1["tokens"]))


def test_data_is_learnable_markov():
    """The stream must be lower-entropy than uniform (so training can show
    measurable ppl drop for the fidelity benchmarks)."""
    cfg, data = tiny_setup()
    b = data.batch(0, 8)
    succ = np.asarray(data.succ)
    toks, labs = np.asarray(b["tokens"]), np.asarray(b["labels"])
    ok = np.zeros_like(labs, bool)
    for br in range(succ.shape[1]):
        ok |= succ[toks, br] == labs
    assert ok.mean() > 0.99  # every label is one of `branching` successors


# -- optimizer / trainer --------------------------------------------------------

def test_adamw_descends_quadratic():
    p = {"w": jnp.ones((8,)) * 5.0}
    ocfg = adamw.OptConfig(lr=0.1, warmup_steps=0, total_steps=100,
                           weight_decay=0.0)
    st = adamw.init(p)
    for _ in range(50):
        g = {"w": 2 * p["w"]}
        p, st, m = adamw.update(g, st, p, ocfg)
    assert float(jnp.abs(p["w"]).max()) < 1.0


def test_train_step_learns():
    cfg, data = tiny_setup()
    ocfg = adamw.OptConfig(lr=3e-3, warmup_steps=5, total_steps=60)
    state = trainer.init_state(jax.random.PRNGKey(0), cfg, ocfg)
    step = jax.jit(trainer.make_train_step(cfg, ocfg, remat=False))
    losses = []
    for i in range(40):
        state, metrics = step(state, data.batch(i, 8))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[::8]


def test_microbatch_equivalent_to_full():
    """Accumulated microbatch grads == full-batch grads (up to bf16 fusion
    reassociation). Post-Adam params are NOT compared: m/sqrt(v) is sign-
    sensitive for near-zero grads, so fp noise there is amplified to ~lr."""
    cfg, data = tiny_setup()
    batch = data.batch(0, 8)
    params = transformer.init_params(jax.random.PRNGKey(1), cfg)

    def loss_fn(p, b):
        return transformer.lm_loss(p, b, cfg, remat=False)[0]

    g_full = jax.grad(loss_fn)(params, batch)
    micro = jax.tree.map(lambda x: x.reshape(4, 2, *x.shape[1:]), batch)
    g_acc = jax.tree.map(jnp.zeros_like, g_full)
    losses = []
    for i in range(4):
        mb = jax.tree.map(lambda x: x[i], micro)
        g = jax.grad(loss_fn)(params, mb)
        g_acc = jax.tree.map(jnp.add, g_acc, g)
    g_acc = jax.tree.map(lambda g: g / 4, g_acc)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_acc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.05, atol=2e-4)


# -- serving -------------------------------------------------------------------

def test_engine_generates_and_modes_differ():
    cfg, data = tiny_setup()
    params = transformer.init_params(jax.random.PRNGKey(2), cfg)
    eng = ContinuousBatchingEngine(params, cfg)
    prompts = [[1, 2, 3, 4], list(range(40))]  # short + long prompt
    study = eng.cot_study(prompts, max_new=16)
    assert set(study) == set(cot.MODES)
    assert study["no_think"]["mean_len"] < study["slow_think"]["mean_len"]
    # auto_think: short prompt -> condensed, long prompt -> full
    auto = study["auto_think"]["generations"]
    assert len(auto[0]) < len(auto[1])
    for mode in cot.MODES:
        for g in study[mode]["generations"]:
            assert all(0 <= t < cfg.vocab for t in g)


def test_repetition_detector():
    assert cot.detect_repetition([1, 2, 3] + [7, 8] * 8)
    assert cot.detect_repetition([5] * 20, max_phrase=4)
    assert not cot.detect_repetition(list(range(40)))
    assert not cot.detect_repetition([1, 2, 1, 3, 1, 4, 1, 5, 1, 6])


# -- checkpoint ------------------------------------------------------------------

def test_checkpoint_roundtrip_and_gc(tmp_path):
    cfg, data = tiny_setup()
    ocfg = adamw.OptConfig()
    state = trainer.init_state(jax.random.PRNGKey(3), cfg, ocfg)
    ck = Checkpointer(str(tmp_path), keep_last=2)
    for s in (1, 2, 3):
        ck.save(s, state, blocking=(s != 3))
    ck.wait()
    assert ck.latest_step() == 3
    assert ck.all_steps() == [2, 3]  # gc dropped step 1
    restored = ck.restore(state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore onto a different device layout (elastic): simulate with a
    1-device NamedSharding target."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    cfg, _ = tiny_setup()
    params = transformer.init_params(jax.random.PRNGKey(4), cfg)
    ck = Checkpointer(str(tmp_path))
    ck.save(7, params, blocking=True)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), params)
    restored = ck.restore(params, shardings=sh)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_resume_training_continuity(tmp_path):
    """Save mid-run, restore, continue: loss trajectory must continue from
    the checkpoint (exact same data via skip-ahead)."""
    cfg, data = tiny_setup()
    ocfg = adamw.OptConfig(lr=3e-3, warmup_steps=0, total_steps=50)
    step = jax.jit(trainer.make_train_step(cfg, ocfg, remat=False))
    state = trainer.init_state(jax.random.PRNGKey(5), cfg, ocfg)
    for i in range(6):
        state, m = step(state, data.batch(i, 4))
        if i == 2:
            ck = Checkpointer(str(tmp_path))
            ck.save(3, state, blocking=True)
    ref_loss = float(m["loss"])
    # resume from step 3 and replay steps 3..5
    state2 = ck.restore(state)
    for i in range(3, 6):
        state2, m2 = step(state2, data.batch(i, 4))
    np.testing.assert_allclose(float(m2["loss"]), ref_loss, rtol=1e-4)
