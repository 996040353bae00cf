"""Chip smoke test: the served near-data skim, once, on one TPU.

Builds a NanoAOD-shaped store in-process from a fixed seed (the
benchmark's shape: 64 trigger bits, 120 filler branches, bitpack codec,
default baskets; 1,000,000 events unless ``--events`` says otherwise),
then serves three queries -- the Higgs-style benchmark selection, a
dielectron invariant-mass window and an electron-jet Delta-R cut --
through ``SkimService(EngineBackend(store))`` on the default executor
(fused, cascaded), once per window and once with ``device_batch=4``.

Every job must finish DONE with survivors and output columns
bit-identical to the staged host reference (``fused=False,
pipeline=False`` on a host-decoded twin of the store); basket decode
must run on the device tier with no fallback, and the fused evaluator
must resolve to the Pallas kernels.  A kernel-level check also pushes
random full-mantissa f32 payload columns through the fused kernel's
one-hot compaction and compares the bit patterns.

The wall times printed are smoke readings, not benchmark results.  The
script refuses to run anywhere but a TPU: with no TPU it exits non-zero
before any other phase and prints no result line.  On success the last
line of stdout is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Run from the repository root::

    python chip_smoke.py [--events N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

MASS_QUERY = {
    "branches": ["Electron_*", "Jet_pt", "MET_*", "run", "event", "luminosityBlock"],
    "selection": {
        "event": [
            {"type": "mass", "collections": ["Electron", "Electron"],
             "window": [60.0, 120.0]},
        ],
    },
}

DELTA_R_QUERY = {
    "branches": ["Electron_*", "Jet_*", "event", "luminosityBlock"],
    "selection": {
        "object": [
            {"collection": "Electron",
             "cuts": [{"var": "pt", "op": ">", "value": 20.0}],
             "min_count": 1},
        ],
        "event": [
            {"type": "deltaR", "collections": ["Electron", "Jet"],
             "op": ">", "value": 0.4},
        ],
    },
}

DEVICE_BATCHES = (None, 4)
SEED = 12  # the benchmark store's generator seed

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def tpu_or_exit():
    """The device check: a TPU, or a non-zero exit before anything else."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r}); "
            "this smoke test runs only on the chip",
            file=sys.stderr,
        )
        sys.exit(2)
    return devices


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from its
    monitoring events: kept apart from the per-query wall time."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration


def queries() -> dict:
    from benchmarks.common import QUERY

    return {"higgs": QUERY, "zee_mass": MASS_QUERY, "e_jet_dr": DELTA_R_QUERY}


def build_store(n_events: int, seed: int, decode_backend: str | None = None):
    from benchmarks.common import N_FILLER, N_HLT
    from repro.data.synth import make_nanoaod_like

    store = make_nanoaod_like(
        n_events, n_hlt=N_HLT, n_filler=N_FILLER, codec="bitpack", seed=seed
    )
    store.decode_backend = decode_backend
    return store


def serve(store, qs: dict, device_batch, clock: CompileClock | None = None) -> dict:
    """Submit each query alone and drain the service; returns
    ``{name: (job, wall_s, compile_s)}``.  The wall time ends when the
    job's output columns are host arrays, i.e. after the device work."""
    from repro.serve.service import EngineBackend, SkimService

    svc = SkimService(EngineBackend(store, device_batch=device_batch))
    out = {}
    for name, q in qs.items():
        c0 = clock.seconds if clock else 0.0
        t0 = time.perf_counter()
        job = svc.submit(q)
        svc.run_until_idle()
        wall = time.perf_counter() - t0
        out[name] = (job, wall, (clock.seconds - c0) if clock else 0.0)
    return out


def host_reference(store_args: tuple, qs: dict) -> dict:
    """The staged evaluator over a host-decoded twin of the store."""
    from repro.core.engine import SkimEngine

    twin = build_store(*store_args, decode_backend="host")
    eng = SkimEngine(twin)
    return {
        name: eng.run(q, mode="near_data", fused=False, pipeline=False)
        for name, q in qs.items()
    }


def _same_bits(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


def output_mismatches(got, want) -> list[str]:
    """Bitwise differences between two skim results' outputs."""
    errs = []
    if got.n_passed != want.n_passed:
        errs.append(f"n_passed {got.n_passed} != reference {want.n_passed}")
    go, wo = got.output, want.output
    go.decode_backend = wo.decode_backend = "host"  # read back independently
    if go.branch_names() != wo.branch_names():
        return [*errs, "output branch lists differ"]
    for name in wo.branch_names():
        if wo.branches[name].jagged:
            same = all(
                _same_bits(x, y)
                for x, y in zip(go.read_jagged(name), wo.read_jagged(name))
            )
        else:
            same = _same_bits(go.read_flat(name), wo.read_flat(name))
        if not same:
            errs.append(f"column {name} differs")
    return errs


def compaction_mismatches(seed: int) -> list[str]:
    """Random finite full-mantissa f32 payload columns through the fused
    kernel's one-hot compaction, compared bit for bit with a host gather."""
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.predicate_eval import Group, Program
    from repro.kernels.ref import GROUP_COUNT, OP_IDS

    rng = np.random.default_rng(seed)
    E, K, D = 4096, 8, 8
    program = Program(
        groups=(Group(GROUP_COUNT, (0,), (OP_IDS[">"],), (0.0,)),),
        term_branches=("x",),
        group_collections=(None,),
        group_weights=(None,),
    )
    terms = rng.normal(size=(1, E, K)).astype(np.float32)
    valid = np.zeros((1, E, K), np.float32)
    valid[:, :, 0] = 1.0
    weights = np.zeros((1, E, K), np.float32)
    payload = (
        rng.normal(size=(E, D)) * 10.0 ** rng.uniform(-8, 8, (E, D))
    ).astype(np.float32)
    payload[:, 0] = np.arange(E, dtype=np.float32)
    packed, count = ops.fused_skim(
        terms, valid, weights, payload, program, use_pallas=True
    )
    want = payload[terms[0, :, 0] > 0]
    got = np.asarray(packed)[: int(count)]
    if not _same_bits(got, want):
        return [f"compaction: {int(count)} rows vs {len(want)} expected, or payload bits differ"]
    return []


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--events", type=int, default=1_000_000,
                    help="events in the generated store (default 1,000,000)")
    args = ap.parse_args(argv)

    devices = tpu_or_exit()
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    from repro import compile_cache
    from repro.core.neardata import default_fused_backend
    from repro.kernels import ops

    cache = compile_cache.enable()
    clock = CompileClock()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache.path}")

    t0 = time.perf_counter()
    store = build_store(args.events, SEED)
    print(f"store: {store.n_events} events, {len(store.branch_names())} branches, "
          f"{store.compressed_bytes()} compressed bytes, built in "
          f"{time.perf_counter() - t0:.3f} s")

    failures = compaction_mismatches(SEED)
    backend = default_fused_backend()
    if backend != "pallas":
        failures.append(f"fused backend resolves to {backend!r}, not 'pallas'")

    qs = queries()
    runs = {}
    for batch in DEVICE_BATCHES:
        ops.reset_dispatch_stats()
        runs[batch] = serve(store, qs, batch, clock)
        st = ops.dispatch_stats()
        for name, (job, wall, comp) in runs[batch].items():
            res = job.result
            print(f"smoke reading, not a benchmark: device_batch={batch} {name}: "
                  f"state {job.state}, {res.n_passed if res else '-'} passed, "
                  f"wall {wall:.6f} s of which compile {comp:.6f} s")
        print(f"device_batch={batch}: {st['compiles']} compiled signatures, "
              f"{st['dispatches']} dispatches, {st['warmups']} warm-ups")

    decode = store.decode_backend_stats()
    print(f"decode tier: {decode}")
    if decode["backend"] != "device" or decode["device_baskets"] == 0:
        failures.append(f"basket decode did not run on the device tier: {decode}")
    if decode["fallbacks"]:
        failures.append(f"{decode['fallbacks']} basket decodes fell back to the host")

    t0 = time.perf_counter()
    ref = host_reference((args.events, SEED), qs)
    print(f"host reference (staged, host decode) in {time.perf_counter() - t0:.3f} s")
    for batch, jobs in runs.items():
        for name, (job, _wall, _comp) in jobs.items():
            tag = f"device_batch={batch} {name}"
            if job.state != "DONE":
                failures.append(f"{tag}: state {job.state}: {job.error}")
                continue
            errs = output_mismatches(job.result, ref[name])
            failures += [f"{tag}: {e}" for e in errs]
            print(f"{tag}: {'MISMATCH' if errs else 'bit-identical'} against the "
                  f"host reference ({ref[name].n_passed} survivors)")
    print(f"compile cache: {cache.hits} hits, {cache.misses} misses; "
          f"compile time {clock.seconds:.3f} s in all")

    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
