#!/usr/bin/env python3
"""Readings that set a cell's limit, on the chip.  For each seed and each
page pool, one run of the cell (its own load, its own window), judged as
a run is, and on the same sample of served requests the float8 control:
the reference with its weights rounded to float8 e4m3 put in the
program's place, judged by the same limits (``bench/check.py``).

  python3 bench/control.py --workload mistral-7b.decode --seeds 1,2,3 \\
      --pools paged,paged_q8

Pool "paged" is the cell as it runs: its widest gap is a lower reading.
Pool "paged_q8" is the program's own int8 page pool, the lower-precision
path of the program: its run's verdict and gap are a control reading.
``--witness KEY=VALUE`` also runs the reference with that configuration
key changed over the same sample (the program's own value of a key it
cannot take from the file), to show what that departure alone reads.
Prints one JSON line per run; the limit lies between the two kinds of
reading (PERF.md section 2).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--pools", default="paged")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--witness", default=None)
    args = ap.parse_args(argv)
    witness = None
    if args.witness:
        k, v = args.witness.split("=")
        witness = {k: float(v)}

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    from bench.run import run_cell
    from bench.spec import Spec

    spec = Spec()
    enable_compile_cache()
    seconds = args.seconds or spec.doc["run_seconds"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        for pool in args.pools.split(","):
            out = run_cell(spec, args.workload, seed, seconds, False,
                           pool=pool, control=True, witness=witness,
                           t_start=time.perf_counter())
            c, ctl = out["compared"], out["control"]
            print(json.dumps({
                "workload": args.workload, "seed": seed, "pool": pool,
                "correct": out["correct"],
                "worst_logit_gap": c["worst_logit_gap"]["value"],
                "fp8_control_correct": ctl["correct"],
                "fp8_control_worst_logit_gap":
                    ctl["compared"]["worst_logit_gap"]["value"],
                "witness_worst_gap": out.get("witness_worst_gap"),
                "metrics": out["metrics"]}), flush=True)
            gc.collect()
            jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
