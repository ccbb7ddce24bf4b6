"""Find an open-loop cell's knee: the highest offered rate at which
completions keep up with arrivals and the queue does not grow over a
window. Runs the cell's traffic at each rate in one process (a fresh
engine per rate) and prints one JSON line per rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 0.4 0.6 0.8

Not part of the benchmark's runs: the rate it finds is written into the
traffic mix's file once, by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from bench import run  # noqa: E402  (sets up sys.path for the program)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    from bench import adapter, drive, stats, traffic
    from repro.launch.jax_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        run.log("the sweep runs only on a TPU")
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = run.load_benchmark(run.ROOT)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(run.ROOT, conf["file"])) as f:
        spec = json.load(f)
    arch = run.load_arch(run.ROOT, spec, conf["file"])
    mix = traffic.load(run.ROOT, cell["traffic"])
    eng = spec["engine"]
    for rate in args.rates:
        server = adapter.Server(conf["name"], spec, args.seed, arch)
        drive.warm_shapes(server, mix["mode"],
                          eng["chunk_pages"] * eng["page_size"])
        m = dict(mix, rate_per_s=rate)
        reqs = traffic.open_loop(m, args.seed, args.seconds,
                                 spec["model"]["vocab_size"])
        marks = {}

        def on_window():
            marks["backlog"] = server.waiting() + len(server.active_rids())

        rec = drive.open_loop(server, reqs, m["mode"], m["warm_s"],
                              args.seconds, 0.0, lambda a, b: None,
                              on_window)
        lo, hi = rec.window
        due = rec.due_in_window()
        done = sum(1 for r in rec.requests if r.done is not None
                   and lo <= r.done < hi)
        ttft = stats.ttfts([r.due for r in due],
                           [r.tokens[0] if r.tokens else None for r in due],
                           rec.stop)
        out = {"rate_per_s": rate, "due_in_window": len(due),
               "completed_in_window": done,
               "backlog_start": marks["backlog"],
               "backlog_end": server.waiting() + len(server.active_rids()),
               "waiting_end": server.waiting(),
               "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
               "ttft_p90_ms": 1e3 * stats.percentile(ttft, 90)}
        print(json.dumps(out), flush=True)
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
