"""Readings that the correctness limits of a cell are set from.

    python bench/calibrate.py --workload <cell> --seeds 12 --control 3 \
        --first-seed <n>

On the chip, at the cell's own size, in one process:

  * the program: one warm-up job, then one job on each of ``--seeds``
    seeds, each held to the reference as a run holds its sampled jobs;
    the worst reading over the seeds of each compared number is its
    lower reading;
  * the control: the reference itself computed in float32 (inputs
    rounded to float32, every sum and integral in float32), in the
    program's place, on ``--control`` seeds; the least reading of each
    number is its upper reading.

Each job prints one JSON line; the last line holds both readings of
every number.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import check, manifest, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    args = ap.parse_args(argv)
    c = manifest.cell(args.workload)
    device = run.device_info(int(c["cell"]["chips"]))
    run.use_cache()
    kind, nums = c["kind"], c["kind"].NUMBERS
    run.run_job(kind, run.job_seeds(args.first_seed, "warmup", 0), c)
    run.persist_compiles(False)

    lower, upper = [], []
    for k in range(args.seeds):
        seeds = run.job_seeds(args.first_seed + k, "window", 0)
        wall, _, results = run.run_job(kind, seeds, c)
        sums = [kind.summarize(r) for r in results]
        del results
        t0 = time.perf_counter()
        refs = kind.reference(seeds, c)
        w = check.worst([kind.gaps(p, r) for p, r in zip(sums, refs)], nums)
        lower.append(w)
        print(json.dumps({"program_seed": args.first_seed + k,
                          "wall_s": wall,
                          "reference_s": time.perf_counter() - t0,
                          "readings": w}), flush=True)
    for k in range(args.control):
        seeds = run.job_seeds(args.first_seed + 10 ** 6 + k, "window", 0)
        refs = kind.reference(seeds, c)
        ctl = kind.reference(seeds, c, dtype=np.float32)
        w = check.worst([kind.gaps(p, r) for p, r in zip(ctl, refs)], nums)
        upper.append(w)
        print(json.dumps({"control_seed": args.first_seed + 10 ** 6 + k,
                          "readings": w}), flush=True)
    print(json.dumps({
        "workload": args.workload, "device": device,
        "lower": {n: max(r[n] for r in lower) for n in nums}
        if lower else None,
        "upper": {n: min(r[n] for r in upper) for n in nums}
        if upper else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
