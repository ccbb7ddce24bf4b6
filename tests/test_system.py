"""End-to-end behaviour tests for the paper's system: the full
train -> calibrate -> PTQ -> quantized CoT serving path on one subject."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_arch, reduced
from repro.core.quant import INT8, W4A8_SMOOTH, calibrate, ptq
from repro.data import DataConfig, SyntheticLM, make_prompts
from repro.models import transformer
from repro.optim import adamw
from repro.serving import ContinuousBatchingEngine, cot
from repro.train import trainer


@pytest.fixture(scope="module")
def system():
    """Train a tiny openPangu-class model until it beats chance, then
    calibrate it (the paper's full pipeline precondition)."""
    cfg = reduced(get_arch("pangu-1b"), groups=2)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=48, seed=0))
    ocfg = adamw.OptConfig(lr=3e-3, warmup_steps=10, total_steps=120)
    state = trainer.init_state(jax.random.PRNGKey(0), cfg, ocfg)
    step = jax.jit(trainer.make_train_step(cfg, ocfg, remat=False))
    first = last = None
    for i in range(120):
        state, m = step(state, data.batch(i, 8))
        first = first if first is not None else float(m["loss"])
        last = float(m["loss"])
    assert last < first - 0.3, (first, last)
    stats = calibrate.collect_stats(state.params,
                                    data.batches(5000, 4, 8), cfg)
    return cfg, state.params, data, stats


def test_full_ptq_serving_pipeline_int8(system):
    """The paper's deployment path end-to-end: INT8 PTQ preserves greedy
    generations almost exactly on a trained model."""
    cfg, params, data, stats = system
    pq = ptq.quantize_model(params, cfg, INT8, stats)
    prompts = make_prompts(DataConfig(vocab=cfg.vocab, seq_len=48), 4, 10)

    eng_fp = ContinuousBatchingEngine(params, cfg)
    eng_q = ContinuousBatchingEngine(pq, cfg, qcfg=INT8, impl="xla")
    out_fp = eng_fp.run(prompts, mode="slow_think", max_new=12)
    out_q = eng_q.run(prompts, mode="slow_think", max_new=12)

    # The chain picks successors uniformly among 4 branches, so greedy
    # argmax sits on near-ties: trajectories may diverge under quant noise
    # (paper Fig. 3 shows the same wording divergence) — the invariant is
    # that INT8 generations stay *task-valid*, not token-identical.
    succ = np.asarray(data.succ)

    def validity(outs):
        ok = tot = 0
        for p_, g in zip(prompts, outs.tokens):
            seq = list(p_) + list(g)
            for a, b in zip(seq[len(p_) - 1:-1], seq[len(p_):]):
                ok += int(b in succ[a]); tot += 1
        return ok / max(tot, 1)

    v_fp, v_q = validity(out_fp), validity(out_q)
    assert v_fp > 0.7, v_fp
    assert v_q >= v_fp - 0.05, (v_fp, v_q)


def test_full_pipeline_all_cot_modes_w4a8(system):
    """W4A8+SmoothQuant serves all three reasoning modes with mode
    semantics intact (budgets ordered, outputs in-vocab)."""
    cfg, params, data, stats = system
    pq = ptq.quantize_model(params, cfg, W4A8_SMOOTH, stats)
    eng = ContinuousBatchingEngine(pq, cfg, qcfg=W4A8_SMOOTH, impl="xla")
    prompts = make_prompts(DataConfig(vocab=cfg.vocab, seq_len=48), 3, 8)
    study = eng.cot_study(prompts, max_new=16)
    assert set(study) == set(cot.MODES)
    assert study["no_think"]["mean_len"] < study["slow_think"]["mean_len"]
    for mode in cot.MODES:
        for g in study[mode]["generations"]:
            assert all(0 <= t < cfg.vocab for t in g)


def test_quantized_model_keeps_task_skill(system):
    """INT8 PTQ must preserve the trained model's next-token skill
    (per-token top-1 accuracy on held-out data within 2% of FP16)."""
    cfg, params, data, stats = system
    pq = ptq.quantize_model(params, cfg, INT8, stats)
    batch = data.batch(7000, 8)
    lf, _ = transformer.forward_train(params, batch, cfg, remat=False)
    lq, _ = transformer.forward_train(pq, batch, cfg, qcfg=INT8,
                                      impl="xla", remat=False)
    # labels are drawn uniformly among `branching` successors, so exact
    # top-1 is capped at 1/branching; the learnable skill is predicting a
    # *valid* successor.
    succ = jnp.asarray(data.succ)
    def valid_rate(logits):
        pred = jnp.argmax(logits, -1)
        return float(jnp.mean(jnp.any(
            succ[batch["tokens"]] == pred[..., None], axis=-1)))
    acc_f, acc_q = valid_rate(lf), valid_rate(lq)
    assert acc_f > 0.6, acc_f               # the model actually learned
    assert acc_q >= acc_f - 0.02, (acc_f, acc_q)
