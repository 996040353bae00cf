"""One run of one cell, from set-up to the result line."""

from __future__ import annotations

import json
import os
import shutil
import sys

from harness import check, drive, spec
from harness.reference import Selection


def _device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def execute(cell, seed: int, seconds: float, traced: bool, devices, t_start: float) -> dict:
    """Run the cell once and return the result object.  ``devices`` are
    the chips the cell uses; ``t_start`` is the process's start on the
    ``perf_counter`` clock."""
    from repro import compile_cache
    from repro.kernels import ops

    cache = compile_cache.enable()
    counter = drive.CompileCounter()
    run = drive.setup(cell, seed, seconds, traced)
    run.peaks = spec.peaks(devices[0].device_kind) if traced else None
    run.kernels = json.load(open(os.path.join(spec.BENCH_DIR, "kernels.json")))
    profile_dir = os.path.join(spec.BENCH_DIR, "out", "profile") if traced else None
    if profile_dir:
        shutil.rmtree(profile_dir, ignore_errors=True)
    print(f"set-up: {len(run.columns.columns)} branches, {run.store.n_events} events, "
          f"{run.store.compressed_bytes()} compressed bytes; compile cache {cache.path}, "
          f"{cache.hits} hits, {cache.misses} misses; {counter.compiles} compiles "
          f"({counter.compile_s:.6f} s)", flush=True)
    ops.reset_dispatch_stats()
    drive.window(run, seconds, counter, profile_dir)
    run.setup_s = run.t0 - t_start
    device = _device_info(devices)
    st = ops.dispatch_stats()
    late = run.lateness
    print(f"window: {len(run.records)} jobs, {run.events_in_window} events delivered, "
          f"{run.compiles_in_window} compiles and {run.traces_in_window} traces inside, "
          f"drain ended {run.t_end - run.t_close:.6f} s after the close; generator late "
          f"by max {max(late, default=0.0):.6f} s, mean {sum(late) / max(len(late), 1):.6f} s; "
          f"{st['dispatches']} dispatches; decode {run.decode_at_cut}", flush=True)
    if traced:
        drive.read_trace(run, profile_dir)
        shutil.rmtree(profile_dir, ignore_errors=True)
        dt = run.device_trace
        device["busy_s"] = dt.busy_s()
        device["window_s"] = dt.window_s

    answers = [check.from_partials(r.doc, r.job.state, r.job.partials) for r in run.records]
    limits = cell.config["checks"]
    numbers = check.compare(answers, run.columns, Selection(run.columns))
    correct = check.verdict(numbers, limits)

    metrics = {}
    for m in cell.metrics:
        if m.end_to_end == traced:
            continue
        value = m.reader.read(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    failed = sum(1 for r in run.records if r.job.state != "DONE")
    result = {
        "correct": bool(correct),
        "attempted": len(run.records),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if traced:
        dt = run.device_trace
        spans = [(e["cat"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6) for e in run.spans]
        from harness.devtrace import label_gaps

        result["breakdown"] = {
            "device_ops": dt.top_ops(10),
            "idle_gaps": label_gaps(dt.gaps(), spans, dt.offset_ns, 10),
        }
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]} for k in check.CHECKS}
    return result


def emit(result: dict) -> None:
    """The result as the last line of stdout, and each compared number
    beside its limit as the last lines of stderr."""
    print(json.dumps(result), flush=True)
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=sys.stderr, flush=True)


def require_chips(n: int):
    """The cell's chips, or exit non-zero with no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        print(f"skimbench: needs {n} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform!r} device(s)", file=sys.stderr)
        sys.exit(2)
    return devices[:n]
