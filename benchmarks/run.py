"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (per the harness contract).

  Fig 4a -> bench_latency      Fig 4b -> bench_breakdown
  Fig 5a -> bench_nearstorage  Fig 5b -> bench_utilization
  (ours)  -> bench_kernels,
             bench_pipeline (serial vs pipelined vs fused-pipelined
             near-data executor: window prefetch overlap + the fused
             predicate/compact device pass), bench_cluster (1->8 node
             scatter-gather scaling + result-cache warm/cold),
             bench_prune (zone-map predicate pushdown: pruned vs
             reference on selective / accept-all / undecidable queries),
             bench_expr (derived-expression tier: Z-window skim, fused
             vs staged and pruned vs reference),
             bench_cascade (cascaded phase-1 execution vs the
             fused+pruned preload path),
             bench_device (device-resident batched cascade: one
             dispatch per window-batch, on-device basket decode,
             survivor masks resident between stages),
             bench_service (async job service: time-to-first-partial
             vs blocking, admission pricing, queue throughput),
             bench_obs (trace/metrics layer: no-op tracer overhead
             bound + deterministic Chrome-trace export of a traced
             service drain),
             bench_faults (fault-tolerance costs: hedged straggler
             makespan, corrupt-basket retry path, checksum overhead
             vs the 2% budget),
             bench_scaling (multi-shard)

Module selection (CI and the 2-core dev host pay for one figure, not the
suite)::

    python benchmarks/run.py --only prune,expr          # just these two
    python benchmarks/run.py --skip kernels             # all but these
    python benchmarks/run.py --only expr --smoke        # shrunken store

``--json [PATH]`` additionally writes every emitted row — modeled times
and bytes moved — to a machine-readable ``BENCH_<pr>.json`` (default
name), the perf-trajectory artifact CI uploads per PR.  After writing,
every realized ``*/wall`` row is compared against the latest committed
``BENCH_<n>.json`` baseline; a >20% regression prints a loud warning
(warning, not failure: realized walls on shared CI cores are noisy —
the deterministic byte/identity contracts live in the benches).
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import re
import sys
import time

# Benchmarks measure the execution path, never the test-time verifier:
# REPRO_VERIFY is forced off here so an ambient setting (e.g. a shell
# that just ran the test suite) cannot skew the modeled-vs-wall rows.
os.environ["REPRO_VERIFY"] = "0"

# The PR this tree's benchmark artifact belongs to (BENCH_<pr>.json).
# The ``PR_NUMBER`` env var overrides the in-tree value; an *empty*
# override fails loudly in main() instead of silently skipping the
# artifact (the PR-9 trajectory gap: no BENCH_9.json was ever emitted).
PR_NUMBER: str | int | None = os.environ.get("PR_NUMBER", 10)


def resolve_pr_number() -> int:
    """The artifact's PR number, or a loud SystemExit when unset."""
    raw = PR_NUMBER
    if raw is None or str(raw).strip() == "":
        raise SystemExit(
            "PR_NUMBER is unset: benchmarks/run.py cannot name its "
            "BENCH_<pr>.json artifact.  Set the PR_NUMBER env var (CI) or "
            "the in-tree default in benchmarks/run.py."
        )
    try:
        return int(str(raw).strip())
    except ValueError:
        raise SystemExit(f"PR_NUMBER={raw!r} is not an integer")


def _modules() -> list[tuple[str, str, str]]:
    """(short name, module attr, figure label) in run order."""
    return [
        ("latency", "bench_latency", "Fig4a latency"),
        ("breakdown", "bench_breakdown", "Fig4b breakdown"),
        ("nearstorage", "bench_nearstorage", "Fig5a near-storage"),
        ("utilization", "bench_utilization", "Fig5b utilization"),
        ("kernels", "bench_kernels", "kernel micro"),
        ("pipeline", "bench_pipeline", "pipelined/fused executor"),
        ("cluster", "bench_cluster", "distributed skim cluster"),
        ("prune", "bench_prune", "zone-map predicate pushdown"),
        ("expr", "bench_expr", "derived-expression tier"),
        ("cascade", "bench_cascade", "cascaded phase-1 execution"),
        ("device", "bench_device", "device-resident batched cascade"),
        ("service", "bench_service", "async skim job service"),
        ("obs", "bench_obs", "trace/metrics layer"),
        ("faults", "bench_faults", "fault tolerance: hedging + checksums"),
        ("scaling", "bench_scaling", "beyond-paper scaling/overlap"),
    ]


def _parse_names(raw: str | None, known: list[str]) -> set[str]:
    if not raw:
        return set()
    names = {n.strip() for n in raw.split(",") if n.strip()}
    unknown = names - set(known)
    if unknown:
        raise SystemExit(
            f"unknown benchmark(s): {sorted(unknown)}; known: {known}"
        )
    return names


#: regression threshold for realized ``*/wall`` rows vs the committed
#: baseline artifact (warn-only: shared-core walls are noisy)
WALL_REGRESSION = 0.20


def _wall_rows(doc: dict) -> dict[str, float]:
    """``name -> value`` for every realized ``*/wall`` row in a BENCH doc."""
    rows: dict[str, float] = {}
    for mod in doc.get("benchmarks", {}).values():
        for row in mod.get("rows", ()):
            name = row.get("name", "")
            if name.endswith("/wall"):
                rows[name] = float(row["value"])
    return rows


def _latest_baseline(pr: int) -> tuple[str, dict] | None:
    """The committed ``BENCH_<n>.json`` with the highest ``n`` below the
    current PR (trace exports and the artifact being written excluded)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    best: tuple[int, str] | None = None
    for fname in os.listdir(root):
        m = re.fullmatch(r"BENCH_(\d+)\.json", fname)
        if not m or int(m.group(1)) >= pr:
            continue
        if best is None or int(m.group(1)) > best[0]:
            best = (int(m.group(1)), fname)
    if best is None:
        return None
    with open(os.path.join(root, best[1])) as fh:
        return best[1], json.load(fh)


def compare_walls(doc: dict, pr: int) -> list[str]:
    """Warn-lines for realized ``*/wall`` rows that regressed >20% vs the
    latest committed baseline artifact (empty list = clean)."""
    base = _latest_baseline(pr)
    if base is None:
        return []
    base_name, base_doc = base
    if bool(base_doc.get("smoke")) != bool(doc.get("smoke")):
        return []  # smoke and full walls are not comparable
    baseline = _wall_rows(base_doc)
    warnings: list[str] = []
    for name, value in sorted(_wall_rows(doc).items()):
        ref = baseline.get(name)
        if ref is None or ref <= 0:
            continue
        if value > ref * (1.0 + WALL_REGRESSION):
            warnings.append(
                f"# WARN wall regression: {name} {value:.1f}us vs "
                f"{ref:.1f}us in {base_name} (+{(value / ref - 1) * 100:.0f}%,"
                f" threshold +{WALL_REGRESSION * 100:.0f}%)"
            )
    return warnings


def main(argv: list[str] | None = None) -> None:
    known = [name for name, _, _ in _modules()]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--only", help=f"comma-separated subset of {known}")
    ap.add_argument("--skip", help="comma-separated modules to leave out")
    ap.add_argument(
        "--smoke", action="store_true",
        help="pass smoke mode (shrunken store) to modules that support it",
    )
    ap.add_argument(
        "--json", nargs="?", const="", default=None, metavar="PATH",
        help="write the emitted rows as machine-readable JSON "
        "(default path: BENCH_<pr>.json from PR_NUMBER)",
    )
    args = ap.parse_args(argv)
    if args.json is not None and not args.json:
        args.json = f"BENCH_{resolve_pr_number()}.json"
    only = _parse_names(args.only, known)
    skip = _parse_names(args.skip, known)
    if only & skip:
        raise SystemExit(f"--only and --skip overlap: {sorted(only & skip)}")

    import benchmarks
    from benchmarks import common
    from repro import compile_cache

    compile_cache.enable()
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    per_module: dict[str, dict] = {}
    for name, attr, label in _modules():
        if (only and name not in only) or name in skip:
            continue
        __import__(f"benchmarks.{attr}")
        mod = getattr(benchmarks, attr)
        print(f"# --- {label} ---", file=sys.stderr)
        kwargs = (
            {"smoke": True}
            if args.smoke and "smoke" in inspect.signature(mod.run).parameters
            else {}
        )
        row0 = len(common.BENCH_ROWS)
        t_mod = time.perf_counter()
        mod.run(**kwargs)
        per_module[name] = {
            "label": label,
            "wall_s": time.perf_counter() - t_mod,
            "rows": common.BENCH_ROWS[row0:],
        }
    total_s = time.perf_counter() - t0
    print(f"# total {total_s:.1f}s", file=sys.stderr)

    if args.json:
        pr = resolve_pr_number()
        doc = {
            "pr": pr,
            "smoke": bool(args.smoke),
            "total_wall_s": total_s,
            "benchmarks": per_module,
        }
        with open(args.json, "w") as fh:
            json.dump(doc, fh, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)
        for line in compare_walls(doc, pr):
            print(line, file=sys.stderr)


if __name__ == "__main__":
    main()
