"""Table 1 analog: FP16 vs INT8 accuracy across the three CoT modes.

Paper claim tested: INT8 preserves >= 90% of FP16 accuracy in every
reasoning mode (openPangu 1B/7B on HumanEval/MBPP -> tiny-trained
openPangu-class model on the synthetic successor task)."""
from __future__ import annotations

import time

from benchmarks import common
from repro.serving import cot


def main(print_rows=True):
    cfg, params, data, stats = common.trained_model()
    variants = common.quantized_variants(cfg, params, stats, names=("int8",))
    engines = common.engines_for(cfg, variants)
    prompts = common.bench_prompts(cfg)

    # logit-level fidelity
    ref = common.eval_logits(params, cfg, data)
    ppl_fp = common.perplexity(ref)
    q = common.eval_logits(variants["int8"][1], cfg, data,
                           qcfg=variants["int8"][0])
    ppl_q = common.perplexity(q)
    top1, kl = common.agreement_and_kl(ref, q)

    rows = []
    accs = {}
    for mode in cot.MODES:
        for name in ("fp16", "int8"):
            t0 = time.time()
            res = engines[name].run(prompts, mode=mode, max_new=24)
            us = (time.time() - t0) / len(prompts) * 1e6
            acc = common.successor_accuracy(data, prompts, res.tokens)
            accs[(mode, name)] = acc
            rows.append(common.row(f"table1/{mode}/{name}/task_acc", us,
                                   f"{acc:.4f}"))
    retention = min(accs[(m, "int8")] / max(accs[(m, "fp16")], 1e-9)
                    for m in cot.MODES)
    rows.append(common.row("table1/ppl_fp16", 0, f"{ppl_fp:.3f}"))
    rows.append(common.row("table1/ppl_int8", 0, f"{ppl_q:.3f}"))
    rows.append(common.row("table1/top1_agreement", 0, f"{top1:.4f}"))
    rows.append(common.row("table1/mean_kl", 0, f"{kl:.5f}"))
    rows.append(common.row("table1/min_mode_retention", 0,
                           f"{retention:.3f}"))
    rows.append(common.row(
        "table1/claim_int8_ge90pct", 0,
        "PASS" if retention >= 0.90 else f"FAIL({retention:.2f})"))
    if print_rows:
        for r in rows:
            print(r)
    return rows


if __name__ == "__main__":
    main()
