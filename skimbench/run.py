"""SkimROOT on-chip benchmark: one run of one cell.

    python3 skimbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, traffic
mix, query templates and metrics are found by name from
``BENCHMARK.json`` (see ``harness/spec.py``).  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from the program's spans and counters and from
a profiler trace of the window.  The last line of stdout is the result
object; the last lines of stderr are the numbers the correctness check
compared, each beside its limit.  Without a TPU (or with fewer chips
than the cell asks for) the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH_DIR]

    from harness import runner, spec

    cell = spec.load_cell(args.workload)
    devices = runner.require_chips(cell.chips)
    result = runner.execute(cell, args.seed, args.seconds, bool(args.trace), devices, T_START)
    runner.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
