import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --------------------------------------------------------------------------
# Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on the
# production meshes, record memory_analysis / cost_analysis / collective
# schedule for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).
#
#   python -m repro.launch.dryrun --arch glm4-9b --shape prefill_32k
#   python -m repro.launch.dryrun --all --jobs 4
#
# The two lines above MUST precede any jax import: jax locks the device
# count at first init, and only the dry-run wants 512 placeholder devices.
# --------------------------------------------------------------------------
import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, cell_supported, get_arch
from repro.core.quant import preset, ptq
from repro.models import transformer
from repro.optim import adamw
from repro.roofline import analysis, hlo_cost
from repro.sharding import rules
from repro.train import trainer
from repro.launch.mesh import make_production_mesh

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")
RESULTS_DIR = os.path.abspath(RESULTS_DIR)


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input (no alloc)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(cfg, b: int, s: int, *, labels: bool) -> dict:
    batch = {}
    if cfg.frontend == "embeddings":
        batch["embeds"] = _sds((b, s, cfg.d_model), jnp.bfloat16)
    else:
        batch["tokens"] = _sds((b, s), jnp.int32)
    if cfg.frontend == "tokens+image":
        batch["ctx"] = _sds((b, cfg.n_ctx_tokens, cfg.d_model), jnp.bfloat16)
    if labels:
        batch["labels"] = _sds((b, s), jnp.int32)
    return batch


def param_specs(cfg, qcfg=None):
    shapes = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    if qcfg is not None:
        shapes = ptq.quantized_param_shapes(shapes, cfg, qcfg)
    return shapes


def input_specs(arch: str, shape_name: str, quant: str = "int8",
                kv_bits: int = 16):
    """All ShapeDtypeStruct inputs for the cell's entry point."""
    cfg = get_arch(arch)
    spec = SHAPES[shape_name]
    b, s = spec.global_batch, spec.seq_len
    if spec.kind == "train":
        state = jax.eval_shape(
            lambda: trainer.init_state(jax.random.PRNGKey(0), cfg,
                                       adamw.OptConfig()))
        return {"state": state, "batch": batch_specs(cfg, b, s, labels=True)}
    qcfg = preset(quant)
    params = param_specs(cfg, qcfg)
    if spec.kind == "prefill":
        return {"params": params,
                "batch": batch_specs(cfg, b, s, labels=False)}
    # decode: one new token against caches of seq_len
    caches = jax.eval_shape(
        lambda: transformer.init_caches(
            jax.eval_shape(lambda: transformer.init_params(
                jax.random.PRNGKey(0), cfg)), cfg, b, s, kv_bits))
    tok = (_sds((b, 1, cfg.d_model), jnp.bfloat16)
           if cfg.frontend == "embeddings" else _sds((b,), jnp.int32))
    return {"params": params, "caches": caches, "token": tok,
            "pos": _sds((b,), jnp.int32)}


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def _act_shardings(mesh, cfg):
    dp = rules._dp(mesh)
    nm = mesh.shape["model"]
    from repro.models.transformer import padded_vocab
    act_mode = os.environ.get("REPRO_ACT_SPEC", "dm")
    if act_mode == "seq":      # sequence-parallel boundary (Megatron-SP)
        act = P(dp, "model", None)
    else:
        act = P(dp, None, "model") if cfg.d_model % nm == 0 else P(dp)
    vpad = padded_vocab(cfg.vocab)
    logits = P(dp, None, "model") if vpad % nm == 0 else P(dp)
    return {"act": NamedSharding(mesh, act),
            "logits": NamedSharding(mesh, logits),
            "moe": NamedSharding(mesh, P(dp, "model"))}


def auto_n_micro(cfg) -> int:
    """Gradient-accumulation depth for train_4k: bounds per-microbatch
    activation memory (the dominant term for wide models) while the f32
    grad accumulator stays params-sized (2-D sharded)."""
    p = cfg.param_count()
    if cfg.d_model >= 6144 or p > 40e9:
        return 8
    if cfg.d_model >= 4096 or p > 8e9:
        return 4
    return 1


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               quant: str = "int8", strategy: str = "fsdp_tp",
               kv_bits: int = 16, n_micro: int = 0, hlo_path: str = None):
    cfg = get_arch(arch)
    spec = SHAPES[shape_name]
    ok, why = cell_supported(cfg, spec)
    if not ok:
        return {"status": "skipped", "reason": why}

    if not n_micro:
        n_micro = auto_n_micro(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    specs = input_specs(arch, shape_name, quant, kv_bits)
    with mesh:
        sh = _act_shardings(mesh, cfg)
        if spec.kind == "train":
            step = trainer.make_train_step(cfg, adamw.OptConfig(),
                                           n_micro=n_micro, remat=True,
                                           shardings=sh)
            state_sh = rules.tree_shardings(mesh, specs["state"], "param",
                                            strategy)
            batch_sh = rules.batch_shardings(mesh, specs["batch"])
            lowered = jax.jit(step, in_shardings=(state_sh, batch_sh),
                              out_shardings=(state_sh, None),
                              donate_argnums=(0,)).lower(
                specs["state"], specs["batch"])
        elif spec.kind == "prefill":
            qcfg = preset(quant)

            def fn(params, batch):
                return transformer.prefill(params, batch, cfg,
                                           max_len=spec.seq_len, qcfg=qcfg,
                                           impl="xla", kv_bits=kv_bits,
                                           shardings=sh)

            p_sh = rules.tree_shardings(mesh, specs["params"], "param",
                                        strategy)
            b_sh = rules.batch_shardings(mesh, specs["batch"])
            lowered = jax.jit(fn, in_shardings=(p_sh, b_sh)).lower(
                specs["params"], specs["batch"])
        else:  # decode
            qcfg = preset(quant)

            def fn(params, caches, token, pos):
                return transformer.decode_step(params, caches, token, pos,
                                               cfg, qcfg=qcfg, impl="xla")

            p_sh = rules.tree_shardings(mesh, specs["params"], "param",
                                        strategy)
            c_sh = rules.tree_shardings(mesh, specs["caches"], "cache")
            t_sh = rules.batch_shardings(mesh, {"t": specs["token"]})["t"]
            pos_sh = rules.batch_shardings(mesh, {"p": specs["pos"]})["p"]
            lowered = jax.jit(fn,
                              in_shardings=(p_sh, c_sh, t_sh, pos_sh),
                              donate_argnums=(1,)).lower(
                specs["params"], specs["caches"], specs["token"],
                specs["pos"])
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    # Archive the partitioned HLO (walker re-analysis without recompiling).
    hlo_text = compiled.as_text()
    if hlo_path:
        import gzip
        os.makedirs(os.path.dirname(hlo_path), exist_ok=True)
        with gzip.open(hlo_path, "wt") as f:
            f.write(hlo_text)
    # Loop-aware walk: XLA cost_analysis counts while (scan) bodies once;
    # the walker multiplies by known_trip_count (flops, bytes, collectives).
    walk = hlo_cost.analyze(hlo_text)
    csum = walk["collectives"]
    mf = analysis.model_flops(cfg, spec.kind, spec.seq_len, spec.global_batch)
    n_chips = 512 if multi_pod else 256
    int8_flops = 0.0
    if spec.kind != "train" and quant in ("int8", "w8a8", "w4a8",
                                          "w4a8-smooth", "w4a8-hadamard"):
        int8_flops = float(mf["linear_fwd_flops"])
    terms = analysis.roofline_terms(
        hlo_flops_per_dev=walk["flops"],
        hlo_bytes_per_dev=walk["bytes"],
        link_bytes_per_dev=float(csum["total_link_bytes"]),
        n_chips=n_chips, int8_linear_flops_global=int8_flops)

    hlo_flops_global = walk["flops"] * n_chips
    return {
        "status": "ok",
        "arch": arch, "shape": shape_name, "kind": spec.kind,
        "mesh": "2x16x16" if multi_pod else "16x16", "n_chips": n_chips,
        "quant": quant if spec.kind != "train" else "bf16",
        "strategy": strategy, "kv_bits": kv_bits,
        "n_micro": n_micro if spec.kind == "train" else None,
        "compile_s": round(compile_s, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_per_device": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "cost": {"flops_per_dev": walk["flops"],
                 "bytes_per_dev": walk["bytes"],
                 "xla_flops_per_dev": float(ca.get("flops", 0.0)),
                 "xla_bytes_per_dev": float(ca.get("bytes accessed", 0.0))},
        "collectives": csum,
        "model_flops": mf,
        "useful_flops_ratio": (mf["model_flops"] / hlo_flops_global
                               if hlo_flops_global else 0.0),
        "roofline": terms,
        "top_bytes": walk.get("top_bytes", []),
    }


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def result_path(arch, shape, multi_pod, quant, strategy, kv_bits, tag=""):
    mesh = "2x16x16" if multi_pod else "16x16"
    suffix = f"__{tag}" if tag else ""
    return os.path.join(
        RESULTS_DIR,
        f"{arch}__{shape}__{mesh}__{quant}__{strategy}__kv{kv_bits}"
        f"{suffix}.json")


def run_one(args) -> int:
    out = result_path(args.arch, args.shape, args.multi_pod, args.quant,
                      args.strategy, args.kv_bits, args.tag)
    if args.cache and os.path.exists(out):
        print(f"[dryrun] cached: {out}")
        return 0
    try:
        hlo_path = out.replace(".json", ".hlo.gz").replace(
            RESULTS_DIR, os.path.join(RESULTS_DIR, "hlo"))
        res = lower_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                         quant=args.quant, strategy=args.strategy,
                         kv_bits=args.kv_bits, n_micro=args.n_micro,
                         hlo_path=hlo_path)
        if args.tag:
            res["tag"] = args.tag
    except Exception as e:  # record failures — they are bugs to fix
        res = {"status": "error", "arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "quant": args.quant, "strategy": args.strategy,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(out, "w") as f:
        json.dump(res, f, indent=1)
    status = res["status"]
    if status == "ok":
        m = res["memory"]["peak_bytes_per_device"] / 2**30
        r = res["roofline"]
        print(f"[dryrun] {args.arch} x {args.shape} ({res['mesh']}, "
              f"{res['quant']}, {args.strategy}): OK "
              f"compile={res['compile_s']}s peak={m:.2f}GiB/dev "
              f"terms(c/m/coll)={r['compute_s']:.4f}/{r['memory_s']:.4f}/"
              f"{r['collective_s']:.4f}s dom={r['dominant']}")
        print(f"  memory_analysis: {res['memory']}")
        print(f"  cost_analysis: {res['cost']}")
    elif status == "skipped":
        print(f"[dryrun] {args.arch} x {args.shape}: SKIP ({res['reason']})")
    else:
        print(f"[dryrun] {args.arch} x {args.shape} "
              f"({'2x16x16' if args.multi_pod else '16x16'}): "
              f"ERROR {res['error']}")
        print(res.get("traceback", "")[-2000:])
    return 0 if status in ("ok", "skipped") else 1


def run_all(args) -> int:
    """Drive every (arch x shape x mesh) as subprocesses (isolation +
    parallelism; each compile gets a fresh XLA)."""
    # per-cell overrides: 90B decode only fits HBM with the int8 KV cache
    kv_override = {("llama32_vision_90b", "decode_32k"): 8}
    jobs = []
    archs = [a for a in ARCH_IDS if not a.startswith("pangu")]
    for arch in archs:
        for shape in SHAPES:
            for mp in (False, True):
                cfg = get_arch(arch)
                ok, _ = cell_supported(cfg, SHAPES[shape])
                quant = "bf16" if SHAPES[shape].kind == "train" else args.quant
                kv = kv_override.get((arch, shape), args.kv_bits)
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--quant", quant, "--strategy", args.strategy,
                       "--kv-bits", str(kv)]
                if mp:
                    cmd.append("--multi-pod")
                if args.cache:
                    cmd.append("--cache")
                jobs.append((arch, shape, mp, cmd, ok))

    running, failures, idx = [], 0, 0
    while idx < len(jobs) or running:
        while idx < len(jobs) and len(running) < args.jobs:
            arch, shape, mp, cmd, ok = jobs[idx]
            idx += 1
            # placeholder-device analysis: children stay on the CPU, so on
            # a TPU host none of them takes the chip
            p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True,
                                 env={**os.environ, "JAX_PLATFORMS": "cpu"})
            running.append((p, arch, shape, mp))
        done = [r for r in running if r[0].poll() is not None]
        for r in done:
            running.remove(r)
            out = r[0].stdout.read()
            sys.stdout.write(out)
            sys.stdout.flush()
            if r[0].returncode != 0:
                failures += 1
        time.sleep(0.5)
    print(f"[dryrun --all] done; {failures} failures")
    return 1 if failures else 0


def reanalyze_all() -> int:
    """Recompute walker-derived costs from archived HLO (no recompiles)."""
    import glob
    import gzip
    n = 0
    for jf in glob.glob(os.path.join(RESULTS_DIR, "*.json")):
        hf = jf.replace(".json", ".hlo.gz").replace(
            RESULTS_DIR, os.path.join(RESULTS_DIR, "hlo"))
        if not os.path.exists(hf):
            continue
        with open(jf) as f:
            res = json.load(f)
        if res.get("status") != "ok":
            continue
        with gzip.open(hf, "rt") as f:
            walk = hlo_cost.analyze(f.read())
        cfg = get_arch(res["arch"])
        mf = res["model_flops"]
        int8_fl = (mf["linear_fwd_flops"] if res["kind"] != "train"
                   and res["quant"] not in ("bf16", "fp16") else 0.0)
        res["cost"]["flops_per_dev"] = walk["flops"]
        res["cost"]["bytes_per_dev"] = walk["bytes"]
        res["collectives"] = walk["collectives"]
        res["roofline"] = analysis.roofline_terms(
            hlo_flops_per_dev=walk["flops"], hlo_bytes_per_dev=walk["bytes"],
            link_bytes_per_dev=float(walk["collectives"]["total_link_bytes"]),
            n_chips=res["n_chips"], int8_linear_flops_global=int8_fl)
        res["useful_flops_ratio"] = (mf["model_flops"]
                                     / (walk["flops"] * res["n_chips"])
                                     if walk["flops"] else 0.0)
        res["top_bytes"] = walk.get("top_bytes", [])
        with open(jf, "w") as f:
            json.dump(res, f, indent=1)
        n += 1
    print(f"[dryrun] reanalyzed {n} cells from archived HLO")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--quant", default="int8",
                    choices=["fp16", "bf16", "int8", "w4a8", "w4a8-smooth",
                             "w4a8-smooth-auto", "w4a8-hadamard"])
    ap.add_argument("--strategy", default="fsdp_tp",
                    choices=["fsdp_tp", "ws", "ws2", "tp"])
    ap.add_argument("--kv-bits", type=int, default=16, choices=[8, 16])
    ap.add_argument("--n-micro", type=int, default=0,
                    help="0 = auto (activation-memory-bounded)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--cache", action="store_true",
                    help="skip cells whose result file already exists")
    ap.add_argument("--tag", default="",
                    help="variant tag appended to the result filename "
                         "(perf-iteration bookkeeping)")
    ap.add_argument("--reanalyze", action="store_true",
                    help="recompute costs from archived HLO, no compiles")
    args = ap.parse_args()
    if args.arch:
        from repro.configs import get_arch as _ga
        args.arch = _ga(args.arch).name     # canonical id for result paths
    if args.quant in ("fp16", "bf16"):
        args.quant = "bf16" if args.quant == "bf16" else "fp16"
    if args.reanalyze:
        sys.exit(reanalyze_all())
    if args.all:
        sys.exit(run_all(args))
    assert args.arch and args.shape, "--arch and --shape (or --all)"
    sys.exit(run_one(args))


if __name__ == "__main__":
    main()
