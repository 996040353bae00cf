"""Record the small chip trace that ``test_devtrace.py`` reduces.

    python3 skimbench/tests/record_trace.py <workload>

Runs a 20,000-event store through one short traced window of a cell's
service on the chip and keeps the profiler's
``.xplane.pb`` (gzipped) and the window's clock readings under
``skimbench/fixtures/``.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def main() -> int:
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH_DIR]
    from harness import devtrace, drive, runner, spec

    cell = spec.load_cell(sys.argv[1])
    runner.require_chips(1)
    cell.config["store"]["n_events"] = 20_000
    counter = drive.CompileCounter()
    run = drive.setup(cell, 12, traced=True)
    tmp = os.path.join(BENCH_DIR, "out", "record")
    shutil.rmtree(tmp, ignore_errors=True)
    drive.window(run, 0.3, counter, tmp)
    out = os.path.join(BENCH_DIR, "fixtures")
    os.makedirs(out, exist_ok=True)
    with open(devtrace.find_xplane(tmp), "rb") as src, gzip.open(
        os.path.join(out, "small.xplane.pb.gz"), "wb", compresslevel=9
    ) as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(out, "small.json"), "w") as fh:
        json.dump({"annotation": drive.ANNOTATION, "t0_ns": int(run.t0 * 1e9),
                   "t1_ns": int(run.t_cut * 1e9)}, fh)
    shutil.rmtree(tmp)
    print(f"recorded: {os.path.getsize(os.path.join(out, 'small.xplane.pb.gz'))} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
