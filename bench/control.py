"""Read the check's two sides on the chip: the program's widest logit gap
and the control's (the plain reference at the configuration's `control`
precision in the program's place), for several seeds in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

Not part of the benchmark's runs; its readings set the limit of the check
(`check.logit_gap_limit` in the configuration's file), as PERF.md records.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from bench import run  # noqa: E402  (sets up sys.path for the program)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    from bench.peaks import peaks
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        run.log("the control runs only on a TPU")
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    for seed in args.seeds:
        res = run.run_cell(run.ROOT, args.workload, seed, args.seconds,
                           False, device=device,
                           peaks=peaks(dev.device_kind), control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          **{k: v["value"] for k, v in
                             res["checks"].items()}}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
