"""The correctness control: the reference, in the precision below the
configuration's, put in the program's place.

    python3 skimbench/control.py --workload <cell> --seeds 1,2,3 [--seconds S]

For each seed it generates the cell's data at the cell's size, takes the
jobs one window of the cell's traffic would submit (the whole-file job
for a closed loop), answers them with the reference computing HT, pair
mass and delta R, and rounding its float outputs, in the configuration's
``control_dtype``, and runs the same comparison the benchmark runs.  One JSON line per seed: every
compared number beside its limit and whether the control failed, which
it has to.  Needs no chip; the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def control_run(cell, seed: int, seconds: float, dtype_name: str | None = None) -> dict:
    import ml_dtypes
    import numpy as np

    from harness import check
    from harness import traffic as tr
    from harness.gen import nanoaod_columns
    from harness.reference import Columns

    st = cell.config["store"]
    cols = Columns(*nanoaod_columns(st, seed))
    if cell.traffic["loop"] == "open":
        jobs = tr.open_schedule(cell.traffic, cell.config["service"]["tenants"], seconds)
    else:
        jobs = [next(tr.closed_jobs(cell.traffic, cell.config["service"]["tenants"]))]
    docs = [tr.query(cell.traffic, cell.templates, j) for j in jobs]
    name = dtype_name or cell.config["control_dtype"]
    dtype = getattr(ml_dtypes, name, None) or getattr(np, name)
    numbers = check.compare(check.control_answers(docs, cols, dtype), cols)
    limits = cell.config["checks"]
    return {
        "seed": seed,
        "dtype": name,
        "jobs": len(docs),
        "failed_as_it_must": not check.verdict(numbers, limits),
        "checks": {k: {"value": numbers[k], "limit": limits[k]} for k in check.CHECKS},
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length for an open loop (default: BENCHMARK.json run_seconds)")
    ap.add_argument("--dtype", default=None,
                    help="precision of the derived quantities (default: the configuration's "
                         "control_dtype; float32 shows what the stated precision reads)")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    from harness import spec

    cell = spec.load_cell(args.workload)
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as fh:
            seconds = json.load(fh)["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_run(cell, seed, seconds, args.dtype)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
