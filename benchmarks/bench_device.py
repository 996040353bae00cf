"""Device-resident batched cascade vs per-window dispatch (DESIGN.md §16).

Same era-correlated conditions store as bench_cascade (zone maps blind,
three of four windows die at the cheap object stage), rebuilt with a
smaller basket so even the smoke run has enough windows to batch.  Three
A/Bs, all on the **device** tier (``fused_backend="xla"`` on this CPU
container; the Pallas route on TPU):

  * **dispatch count** — the per-window executor pays one device
    dispatch per (window, stage, alive-span); the batched executor pays
    one per (batch, stage): O(windows) -> O(windows/B).  Read from the
    engine's ``device_dispatches`` ledger, asserted reduced >= 4x.
  * **realized wall** — ``pipeline="threads"`` end-to-end host
    wall-clock, best-of-N, batched asserted >= 1.5x faster (the
    acceptance contract: dispatch overhead, not predicate math,
    dominates the per-window device path).
  * **decode tier** — on-device basket decode (``decode_backend=
    "device"``, the jitted codec mirror on CPU) vs the host numpy
    codec, bit-identical by contract; a zlib store shows the
    test-visible host fallback (``decode_fallbacks``).

Survivor sets are asserted bit-identical between the two executors
(and against the staged reference pinned by bench_cascade's workload).

``--smoke`` shrinks the store for CI.
"""

from __future__ import annotations

import os
import sys
import tempfile
import time

import numpy as np

from benchmarks import common
from benchmarks.bench_cascade import QUERY, _make_store
from benchmarks.common import csv_row
from repro.core.engine import SkimEngine, WAN_1G
from repro.data.store import EventStore

REPEATS = 5
BASKET = 1024  # smaller than bench_cascade's 4096: more windows to batch
BATCH = 16


def _get_store(n_events: int) -> EventStore:
    from repro.data.store import ZONEMAP_VERSION

    path = os.path.join(
        tempfile.gettempdir(),
        f"repro_bench_device_z{ZONEMAP_VERSION}_b{BASKET}_{n_events}.skim",
    )
    if os.path.exists(path):
        return EventStore.load(path)
    st = _make_store(n_events, basket_events=BASKET)
    st.save(path)
    return st


def _survivors(res) -> tuple:
    ev = res.output.read_flat("event")
    return (res.n_passed, int(ev.sum()), tuple(ev[:16].tolist()))


def _best(engine, repeats: int = REPEATS) -> dict:
    best = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = engine.run(QUERY, "near_data", pipeline="threads")
        wall = time.perf_counter() - t0
        if best is None or wall < best["wall_s"]:
            best = {
                "wall_s": wall,
                "dispatches": res.extras["device_dispatches"],
                "survivors": _survivors(res),
                "bytes": res.stats.bytes_fetched,
                "windows": len(res.extras["window_rows"]),
            }
    return best


def _bench_decode(store: EventStore) -> None:
    """On-device vs host basket decode A/B over the heavy filter branch."""
    name = "Track_pt"
    blobs = list(store._blobs[name])
    arms: dict[str, tuple[float, list]] = {}
    for backend in ("host", "device"):
        probe = store
        probe.decode_backend = backend
        probe._decode_backend_resolved = None
        probe.decode_cache_baskets = 0  # measure the codec, not the LRU
        probe.decode_device_baskets = probe.decode_host_baskets = 0
        probe.decode_fallbacks = 0
        probe.decode_blobs(name, blobs[:2])  # warm (jit compile on device)
        t0 = time.perf_counter()
        out = probe.decode_blobs(name, blobs)
        arms[backend] = (time.perf_counter() - t0, out)
        stats = probe.decode_backend_stats()
        assert stats["backend"] == backend, stats
        assert stats["fallbacks"] == 0, ("bitpack decode must not fall back", stats)
    store.decode_backend = None
    store._decode_backend_resolved = None
    host_s, host_out = arms["host"]
    dev_s, dev_out = arms["device"]
    for a, b in zip(host_out, dev_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    n = len(blobs)
    csv_row("device/decode/host", host_s * 1e6, f"{n} baskets, numpy codec")
    csv_row(
        "device/decode/device", dev_s * 1e6,
        f"{n} baskets, one launch per header group, one read-back; bit-identical",
    )

    # the fallback contract: a non-bitpack store asked for device decode
    # degrades to host, visibly
    zl = _make_store(4 * BASKET, basket_events=BASKET)
    arrs = {nm: zl.read_flat(nm) for nm in ("MET_pt", "event")}
    zstore = EventStore.from_arrays(
        arrs, basket_events=BASKET, codec="zlib", decode_backend="device"
    )
    zstore.read_flat("MET_pt")
    zstats = zstore.decode_backend_stats()
    assert zstats["fallbacks"] > 0, ("zlib fallback must be ledgered", zstats)
    csv_row(
        "device/decode/fallbacks", zstats["fallbacks"],
        "zlib store on device tier -> host codec, counted",
    )


def run(smoke: bool = False) -> dict:
    # pinned smoke size (not the possibly-clamped common.N_EVENTS): the
    # dispatch A/B needs enough windows for several batches regardless
    # of which modules ran earlier in the suite
    n_events = 40_000 if smoke else common.N_EVENTS
    store = _get_store(n_events)

    per_window = SkimEngine(
        store, input_link=WAN_1G, chunk_events=BASKET, fused_backend="xla"
    )
    batched = SkimEngine(
        store, input_link=WAN_1G, chunk_events=BASKET, fused_backend="xla",
        device_batch=BATCH,
    )
    # warm jit/page caches on both engines so walls are steady-state
    per_window.run(QUERY, "near_data", pipeline="threads")
    batched.run(QUERY, "near_data", pipeline="threads")

    ref = _best(per_window)
    bat = _best(batched)

    assert bat["survivors"] == ref["survivors"], (
        "batched cascade changed the survivor set", bat, ref,
    )
    csv_row(
        "device/per_window/wall", ref["wall_s"] * 1e6,
        f"{ref['windows']} windows, {ref['dispatches']} device dispatches",
    )
    csv_row(
        "device/batched/wall", bat["wall_s"] * 1e6,
        f"B={BATCH}, {bat['dispatches']} device dispatches",
    )
    speedup = ref["wall_s"] / max(bat["wall_s"], 1e-12)
    csv_row(
        "device/batched/speedup", speedup,
        "x realized (threads), batched vs per-window dispatch",
    )
    reduction = ref["dispatches"] / max(bat["dispatches"], 1)
    csv_row(
        "device/batched/dispatch_reduction", reduction,
        f"{ref['dispatches']} -> {bat['dispatches']} dispatches/query",
    )
    # acceptance: O(windows) -> O(windows/B) dispatches and a real wall
    # win — the per-window device path pays per-dispatch overhead the
    # batched path amortizes
    assert reduction >= 4.0, (
        "batched cascade must cut device dispatches >= 4x", ref, bat,
    )
    assert speedup >= 1.5, (
        "batched cascade must be >= 1.5x faster realized", ref, bat,
    )

    _bench_decode(store)
    return {"per_window": ref, "batched": bat}


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run(smoke="--smoke" in sys.argv[1:])
