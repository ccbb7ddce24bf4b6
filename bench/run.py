"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (`BENCHMARK.json` "workloads") names a configuration file and a
traffic mix; the harness finds everything else by name: the architecture
the configuration names under "arch" in `bench/arch/<arch>/`, the mix's
data in `bench/traffic/<mix>.json`, each metric's reader in
`bench/metrics/<metric>.py`. It makes the weights from the seed, warms up the
cell's programs, drives the traffic for `--seconds`, checks what the timed
path produced against the plain reference, and prints one JSON line: with
`--trace 0` the cell's end-to-end metrics, with `--trace 1` its per-layer
metrics (from a profiler trace of a slice of the window that holds both
step programs, and the run's records). Without a TPU, or with fewer chips
than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TRACE_S = 3.0           # least length of the traced slice, mid-window
DRAIN_S = 30.0          # open loop: longest wait for first tokens after it


@dataclasses.dataclass
class Context:
    """What a metric's reader gets."""
    record: object
    model: dict
    quant: dict
    engine: dict
    peaks: dict
    setup_s: float
    trace: Optional[dict] = None
    arch: object = None               # the configuration's architecture


class CompileMeter:
    """Backend compiles (loads from the persistent cache included) and
    persistent-cache hits and misses, read off JAX's monitoring events."""

    def __init__(self, jax):
        self.compiles, self.seconds, self.hits, self.misses = 0, 0.0, 0, 0

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.seconds += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_reader(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_arch(root: str, spec: dict, where: str = "the configuration"):
    """The architecture that a configuration names under its top-level
    "arch": the package `bench/arch/<arch>/` under `root`, loaded once per
    directory. A configuration without the key, or naming a package that
    is not there, is an error that says which."""
    base = os.path.join(root, "bench", "arch")
    have = sorted(d for d in (os.listdir(base) if os.path.isdir(base)
                              else [])
                  if os.path.isfile(os.path.join(base, d, "__init__.py")))
    if "arch" not in spec:
        raise ValueError(f"{where} names no architecture: it needs a "
                         f"top-level \"arch\", one of {have} "
                         f"(bench/arch/<arch>/)")
    if spec["arch"] not in have:
        raise ValueError(f"{where} names architecture {spec['arch']!r}, "
                         f"which {base} does not hold; it holds {have}")
    path = os.path.realpath(os.path.join(base, spec["arch"]))
    name = "bench_arch_" + hashlib.sha1(path.encode()).hexdigest()[:16]
    if name not in sys.modules:
        found = importlib.util.spec_from_file_location(
            name, os.path.join(path, "__init__.py"),
            submodule_search_locations=[path])
        mod = importlib.util.module_from_spec(found)
        sys.modules[name] = mod
        try:
            found.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metrics this cell reports in a run of this kind."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(root: str, cell_name: str, seed: int, seconds: float,
             trace: bool, *, device: dict, peaks: dict,
             persistent_cache: bool = True, control: bool = False) -> dict:
    """Set up, drive, check; the result line as a dict. With `control`
    the check also reads the control's gap (bench/control.py)."""
    import jax
    from bench import adapter, check, drive, traffic
    from bench import trace as trace_mod

    bench = load_benchmark(root)
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, conf["file"])) as f:
        spec = json.load(f)
    arch = load_arch(root, spec, conf["file"])
    mix = traffic.load(root, cell["traffic"])
    if persistent_cache:
        from repro.launch.jax_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    meter = CompileMeter(jax)

    server = adapter.Server(conf["name"], spec, seed, arch)
    jax.block_until_ready(server.engine.params)
    eng = spec["engine"]
    drive.warm_shapes(server, mix["mode"],
                      eng["chunk_pages"] * eng["page_size"])
    vocab = spec["model"]["vocab_size"]
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

    def tracer_for(w0, w1):
        if not trace:
            return None
        mid = (w0 + w1 - TRACE_S) / 2
        return drive.Tracer(tmp, max(w0, mid), TRACE_S, w1)

    marks = {}

    def on_window():
        marks["t"] = time.time()
        marks["compiles"], marks["hits"] = meter.compiles, meter.hits

    if mix["loop"] == "open":
        reqs = traffic.open_loop(mix, seed, seconds, vocab, DRAIN_S)
        rec = drive.open_loop(server, reqs, mix["mode"], mix["warm_s"],
                              seconds, DRAIN_S, tracer_for, on_window)
    else:
        n = (eng["max_batch"] if mix["sessions"] == "max_batch"
             else mix["sessions"])
        reqs = traffic.closed_loop(mix, seed, n, vocab)
        rec = drive.closed_loop(server, reqs, n, mix["mode"], seconds,
                                tracer_for, on_window)
    in_window = meter.compiles - marks["compiles"]
    if rec.stalls:
        log("profiler start and stop blocked the loop for "
            f"{[b - a for a, b in rec.stalls]!r} s")
    log(f"compiles inside the window: {in_window}, persistent-cache loads "
        f"inside it: {meter.hits - marks['hits']}; persistent cache hits "
        f"{meter.hits}, misses {meter.misses}; compile or load "
        f"{meter.seconds!r} s in all")
    log(f"KV pool: at most {rec.held_tokens} tokens held in the window, of "
        f"{eng['n_pages'] * eng['page_size']} the pool reserves")
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=stats.get("peak_bytes_in_use"))
    reduced = None
    if trace:
        reduced = trace_mod.reduce(trace_mod.load(tmp))
        shutil.rmtree(tmp, ignore_errors=True)
        if reduced:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])

    quant = dict(spec["quant"], page_size=eng["page_size"])
    ctx = Context(rec, spec["model"], quant, eng, peaks,
                  marks["t"] - PROCESS_START, reduced, arch)
    metrics = {}
    for m in cell_metrics(bench, cell_name, trace):
        value = load_reader(root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the check: served tokens of a sample of the finished requests
    finished = [{"rid": r.rid, "n_out": r.n_out,
                 "prompt": server.prompt(r.rid), "served": server.out(r.rid)}
                for r in rec.requests if r.done is not None]
    wrong = sum(len(r["served"]) != r["n_out"]
                or not all(0 <= t < vocab for t in r["served"])
                for r in finished)
    lo, hi = rec.window
    attempted = (len(rec.due_in_window()) if mix["loop"] == "open" else
                 sum(1 for r in rec.requests
                     if r.submit < hi and (r.done is None or r.done >= lo)))
    server.close()
    del server
    gc.collect()
    t_ref = time.time()
    got = check.gaps(arch, seed, spec, check.sample(finished, seed),
                     control=control)
    log(f"reference took {time.time() - t_ref!r} s")
    limit = spec["check"]["logit_gap_limit"]
    checks = {                       # each number beside its limit
        "finished": {"value": len(finished), "min": 1},
        "wrong_lengths": {"value": wrong, "max": 0},
        "tokens_compared": {"value": got["tokens_compared"], "min": 1},
        "logit_gap": {"value": got["logit_gap"], "max": limit},
    }
    if control:         # the control's tokens, judged in the program's place
        checks["control_gap"] = {"value": got["control_gap"], "max": limit}
    correct = all(c["value"] <= c["max"] if "max" in c
                  else c["value"] >= c["min"] for c in checks.values())
    result = {"correct": bool(correct), "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if trace and reduced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark(ROOT)
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    import jax
    from bench.peaks import peaks
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"needs {cell['chips']} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)")
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), device=device,
                      peaks=peaks(devs[0].device_kind))
    for name, c in result["checks"].items():
        bound = (f"at most {c['max']!r}" if "max" in c
                 else f"at least {c['min']!r}")
        log(f"check {name}: {c['value']!r} ({bound})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
