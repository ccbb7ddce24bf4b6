"""End-to-end serving driver (the paper's deployment scenario):

  1. train a small openPangu-class model on the synthetic stream,
  2. calibrate + post-training-quantize it to INT8 (W8A8),
  3. serve batched requests under all three CoT reasoning modes,
  4. report per-mode accuracy/length/repetition, FP16 vs INT8.

    PYTHONPATH=src python examples/serve_quantized.py [--steps 200]
"""
import argparse
import time

import jax

from repro.configs import get_arch, reduced
from repro.core.quant import INT8, calibrate, ptq
from repro.data import DataConfig, SyntheticLM, make_prompts
from repro.optim import adamw
from repro.serving import ContinuousBatchingEngine
from repro.train import trainer

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--requests", type=int, default=8)
ap.add_argument("--max-new", type=int, default=24)
args = ap.parse_args()

# -- 1. train ---------------------------------------------------------------
cfg = reduced(get_arch("pangu-1b"), groups=2)
data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64, seed=0))
ocfg = adamw.OptConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps)
state = trainer.init_state(jax.random.PRNGKey(0), cfg, ocfg)
step = jax.jit(trainer.make_train_step(cfg, ocfg, remat=False))
t0 = time.time()
for i in range(args.steps):
    state, m = step(state, data.batch(i, 16))
print(f"[1] trained {args.steps} steps in {time.time() - t0:.0f}s, "
      f"loss {float(m['loss']):.3f}")

# -- 2. calibrate + PTQ -------------------------------------------------------
stats = calibrate.collect_stats(state.params, data.batches(9000, 6, 16), cfg)
params_q = ptq.quantize_model(state.params, cfg, INT8, stats)
print(f"[2] PTQ int8 done ({len(stats)} calibrated sites)")

# -- 3+4. serve both precisions across CoT modes ------------------------------
prompts = make_prompts(DataConfig(vocab=cfg.vocab, seq_len=64),
                       args.requests, 12)
for name, (q, p) in {"fp16": (None, state.params),
                     "int8": (INT8, params_q)}.items():
    eng = ContinuousBatchingEngine(p, cfg, qcfg=q,
                                   impl="xla" if q else None)
    study = eng.cot_study(prompts, max_new=args.max_new)
    for mode, r in study.items():
        print(f"[{name}] {mode:11s} mean_len={r['mean_len']:5.1f} "
              f"repetition={r['repetition_rate']:.2f} "
              f"sample={r['generations'][0][:8]}")
print("OK — quantized CoT serving end-to-end")
