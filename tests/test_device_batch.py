"""Device-resident batched cascade + on-device basket decode (DESIGN.md §16).

The acceptance contract of the window-batched device path:

  * batched cascade runs are **bit-identical** on survivors to the
    per-window reference for batch sizes {1, 3, all} — across the
    engine (serial and threaded), the shared-scan batch engine, and
    the cluster scatter-gather path;
  * shape buckets are grow-only: a window sweep whose padded object
    multiplicity (``pad_K``) grows late re-compiles once per bucket
    growth, then the compiled-program counter is pinned (no
    per-batch recompiles);
  * on-device basket decode round-trips every bitpack kind — zigzag
    ints, xor-prefix floats, bools, raw-f32 bail-outs — bit-identically
    to the host codec, including non-word-aligned basket tails, and a
    whole fetch round decodes in one launch per group of like baskets
    and one read-back, with the decode cache's and tier's ledgers as
    one round per branch left them;
  * without an accelerator the decode tier resolves to host, and a
    device request over a codec with no device path falls back loudly
    (``decode_fallbacks``) instead of silently.
"""

import numpy as np
import pytest

from repro.cluster import build_cluster
from repro.core.engine import Breakdown, run_skim
from repro.core.plan import CascadeExecutor
from repro.core.planner import plan_skim
from repro.core.query import parse_query
from repro.data import codecs
from repro.data.store import EventStore, FetchStats
from repro.data.synth import make_nanoaod_like
from repro.kernels import ops
from repro.serve.engine import SharedScanEngine

N_EVENTS = 12_000
BASKET = 2048

QUERY = {
    "branches": ["Electron_*", "MET_*", "event", "luminosityBlock"],
    "selection": {
        "preselection": [
            {"branch": "luminosityBlock", "op": "<=", "value": 2}
        ],
        "object": [
            {
                "collection": "Electron",
                "cuts": [
                    {"var": "pt", "op": ">", "value": 15.0},
                    {"var": "eta", "op": "abs<", "value": 2.4},
                ],
                "min_count": 1,
            }
        ],
        "event": [
            {"type": "any", "branches": ["HLT_IsoMu24", "HLT_absent_path"]},
            {"type": "cut", "branch": "MET_pt", "op": ">", "value": 15.0},
        ],
    },
}

SECOND = {
    "branches": ["MET_*", "event"],
    "selection": {
        "preselection": [{"branch": "MET_pt", "op": ">", "value": 21.0}]
    },
}


@pytest.fixture(scope="module")
def store():
    return make_nanoaod_like(
        N_EVENTS, n_hlt=16, n_filler=8, basket_events=BASKET
    )


@pytest.fixture(scope="module")
def reference(store):
    return run_skim(
        store, QUERY, mode="near_data", fused=False, pipeline=False,
        prune=False, cascade=False,
    )


def _assert_same_output(res, ref):
    assert res.n_passed == ref.n_passed
    assert res.n_input == ref.n_input
    for name in ref.output.branch_names():
        br = ref.output.branches[name]
        if br.jagged:
            v0, c0 = ref.output.read_jagged(name)
            v1, c1 = res.output.read_jagged(name)
            np.testing.assert_array_equal(c1, c0)
            np.testing.assert_array_equal(v1, v0)
        else:
            np.testing.assert_array_equal(
                res.output.read_flat(name), ref.output.read_flat(name)
            )


# ---------------------------------------------------------------------------
# batched-cascade bit-identity: engine / shared-scan / cluster
# ---------------------------------------------------------------------------

# "all": larger than the window count, so one batch covers the sweep
ALL = N_EVENTS // BASKET + 1


@pytest.mark.parametrize("device_batch", [1, 3, ALL])
@pytest.mark.parametrize("pipeline", [False, "threads"])
def test_batched_engine_bit_identical(store, reference, device_batch, pipeline):
    res = run_skim(
        store, QUERY, mode="near_data", pipeline=pipeline, prune=False,
        cascade=True, device_batch=device_batch,
    )
    _assert_same_output(res, reference)
    assert res.extras["device_batch"] == device_batch
    assert "device_dispatches" in res.extras


@pytest.mark.parametrize("device_batch", [1, 3, ALL])
def test_batched_engine_ledger_exact(store, device_batch):
    """fetched + skipped == the preload reference's fetched bytes, even
    under batching (the batch ledger dedups exactly like per-window)."""
    preload = run_skim(
        store, QUERY, mode="near_data", pipeline=False, prune=False,
        cascade=False,
    )
    res = run_skim(
        store, QUERY, mode="near_data", pipeline=False, prune=False,
        cascade=True, device_batch=device_batch,
    )
    assert (
        res.stats.bytes_fetched + res.stats.cascade_bytes_skipped
        == preload.stats.bytes_fetched
    )


@pytest.mark.parametrize("device_batch", [1, 3, ALL])
def test_batched_shared_scan_bit_identical(store, device_batch):
    batch = SharedScanEngine(
        store, cascade=True, device_batch=device_batch
    ).run_batch([QUERY, SECOND])
    ref = SharedScanEngine(store, cascade=True).run_batch([QUERY, SECOND])
    for res, solo in zip(batch.results, ref.results):
        _assert_same_output(res, solo)
    assert batch.shared_stats.bytes_fetched == ref.shared_stats.bytes_fetched


@pytest.mark.parametrize("device_batch", [1, 3, ALL])
def test_batched_cluster_bit_identical(store, reference, device_batch):
    coord = build_cluster(
        store, 3, replication=False, cascade=True, device_batch=device_batch
    )
    _assert_same_output(coord.run(QUERY), reference)


def test_device_batch_validated(store):
    with pytest.raises(ValueError):
        run_skim(store, QUERY, device_batch=0)
    with pytest.raises(ValueError):
        SharedScanEngine(store, device_batch=-2)
    with pytest.raises(ValueError):
        run_skim(store, QUERY, fused_backend="cuda")


# ---------------------------------------------------------------------------
# recompile regression: grow-only shape buckets
# ---------------------------------------------------------------------------


def _spiky_store() -> EventStore:
    """Last window's electron multiplicity is ~8x the rest: ``pad_K``
    grows only on the final batch of a sweep."""
    rng = np.random.default_rng(5)
    n = 8 * BASKET
    lam = np.where(np.arange(n) < n - BASKET, 1.2, 10.0)
    n_el = rng.poisson(lam).astype(np.int32)
    tot = int(n_el.sum())
    cols = {
        "nElectron": n_el,
        "Electron_pt": (rng.exponential(25.0, tot) + 3.0).astype(np.float32),
        "Electron_eta": rng.uniform(-2.5, 2.5, tot).astype(np.float32),
        "MET_pt": (rng.exponential(30.0, n) + 1.0).astype(np.float32),
        "HLT_IsoMu24": rng.random(n) < 0.3,
        "event": np.arange(n, dtype=np.int32),
        "luminosityBlock": (np.arange(n) // 1000).astype(np.int32),
    }
    jagged = {"Electron_pt": "nElectron", "Electron_eta": "nElectron"}
    return EventStore.from_arrays(cols, jagged=jagged, basket_events=BASKET)


def _run_sweep(ex, store, batch: int):
    outs = []
    windows = [
        (a, min(a + BASKET, store.n_events))
        for a in range(0, store.n_events, BASKET)
    ]
    for i in range(0, len(windows), batch):
        entries = [
            (a, b, None, Breakdown(), FetchStats(), {})
            for a, b in windows[i : i + batch]
        ]
        outs.extend(ex.run_window_batch(entries, pad_B=batch))
    return outs


def test_recompile_count_pinned_with_late_growing_pad_k():
    store = _spiky_store()
    plan = plan_skim(parse_query(QUERY), store, cascade=True)
    ex = CascadeExecutor(plan, store, adaptive=False, backend="xla")
    ops.reset_dispatch_stats()
    first = _run_sweep(ex, store, batch=3)
    compiles_after_first = ops.dispatch_stats()["compiles"]
    assert compiles_after_first > 0
    # the last batch grew the pad_K bucket once; the buckets are now
    # saturated — a second identical sweep must not compile anything
    second = _run_sweep(ex, store, batch=3)
    stats = ops.dispatch_stats()
    assert stats["compiles"] == compiles_after_first, stats
    # it must still dispatch (cache reuse, not short-circuit) ...
    assert stats["dispatches"] > 0
    # ... and stay bit-identical between sweeps
    for o1, o2 in zip(first, second):
        np.testing.assert_array_equal(o1.mask, o2.mask)


def test_warmups_ledgered_outside_dispatches():
    """Shape-bucket warm-up dispatches are counted separately so stage
    timers (and the device_dispatches ledger) see steady state only."""
    store = _spiky_store()
    plan = plan_skim(parse_query(QUERY), store, cascade=True)
    ex = CascadeExecutor(plan, store, adaptive=False, backend="xla")
    ops.reset_dispatch_stats()
    _run_sweep(ex, store, batch=3)
    stats = ops.dispatch_stats()
    assert stats["warmups"] > 0
    assert stats["dispatches"] > 0


# ---------------------------------------------------------------------------
# on-device basket decode: round-trip every kind, any tail
# ---------------------------------------------------------------------------


def _kind_values(kind: str, n: int, rng) -> np.ndarray:
    if kind == "int":
        return rng.integers(-500, 2_000_000, n).astype(np.int32)
    if kind == "bool":
        return rng.random(n) < 0.37
    if kind == "float":
        # low-entropy mantissas: xor-prefix packing stays under the
        # raw-f32 bail-out threshold
        return (rng.integers(0, 64, n).astype(np.float32) * 0.25 + 8.0)
    if kind == "raw":
        # full-entropy floats trip the bail-out (KIND_RAW_F32 passthrough)
        return rng.random(n).astype(np.float32) * 1e3
    raise AssertionError(kind)


@pytest.mark.parametrize("kind,dtype", [
    ("int", np.int32), ("bool", np.bool_),
    ("float", np.float32), ("raw", np.float32),
])
@pytest.mark.parametrize("n", [1024, 1001, 777, 333, 32, 1])
def test_device_decode_round_trip(kind, dtype, n):
    rng = np.random.default_rng(11)
    values = _kind_values(kind, n, rng)
    blob = codecs.bitpack_encode(values)
    if kind == "raw" and n >= 32:
        # (a 1-element basket xor-prefixes to zero bits and legitimately
        # stays KIND_FLOAT — the round-trip below still must hold)
        assert codecs.bitpack_raw_parts(blob)["kind"] == codecs.KIND_RAW_F32
    host = codecs.bitpack_decode(blob, dtype)
    np.testing.assert_array_equal(host, values.astype(dtype))
    [dev] = codecs.decode_basket_batch([blob], "bitpack", dtype, backend="device")
    assert np.asarray(dev).dtype == host.dtype
    np.testing.assert_array_equal(np.asarray(dev), host)


def test_device_decode_mixed_kind_batch():
    """One decode round over a mixed-kind, mixed-tail blob list, each
    blob with its own dtype."""
    rng = np.random.default_rng(3)
    cases = [
        ("int", np.int32, 1001), ("bool", np.bool_, 777),
        ("float", np.float32, 333), ("raw", np.float32, 501),
        ("int", np.int32, 2048), ("float", np.float32, 64),
    ]
    blobs = [codecs.bitpack_encode(_kind_values(k, n, rng)) for k, _, n in cases]
    dtypes = [dt for _, dt, _ in cases]
    got = codecs.decode_basket_batch(blobs, "bitpack", dtypes, backend="device")
    for blob, dt, arr in zip(blobs, dtypes, got):
        np.testing.assert_array_equal(arr, codecs.bitpack_decode(blob, dt))


def _round_blobs(rng) -> tuple[list, list]:
    """Blobs of one fetch round as a store hands them over: flat baskets
    of 2,048 values and jagged ones of 4-6x as many, ints of several bit
    widths, floats that stay bit-planes and floats stored as literals,
    bools and empty baskets."""
    specs = []
    for n in (2048, 9000, 12500):
        for span in (6, 200, 70_000, 2**27):
            specs.append((rng.integers(0, span, n).astype(np.int32), np.int32))
        specs.append((rng.random(n) < 0.3, np.bool_))
        specs.append((_kind_values("float", n, rng), np.float32))
        specs.append((_kind_values("raw", n, rng), np.float32))
    specs.append((np.zeros(0, np.int32), np.int32))
    specs.append((np.zeros(0, np.float32), np.float32))
    specs.append((rng.integers(0, 6, 2048).astype(np.int32), np.int32))
    order = rng.permutation(len(specs))
    return (
        [codecs.bitpack_encode(specs[i][0]) for i in order],
        [specs[i][1] for i in order],
    )


@pytest.mark.parametrize("use_pallas", [False, True], ids=["mirror", "pallas"])
def test_device_decode_round_groups_like_baskets(use_pallas):
    """A round is bit-identical to the per-blob host reference, and
    decodes in one launch per (kind, dtype, plane bucket, word bucket)
    group and one read-back of all of them."""
    from repro.obs import Tracer
    from repro.serve import ManualClock

    blobs, dtypes = _round_blobs(np.random.default_rng(21))
    parts = [codecs.bitpack_raw_parts(b) for b in blobs]
    tr = Tracer(clock=ManualClock())
    got = ops.basket_decode_batch(parts, dtypes, use_pallas=use_pallas, tracer=tr)
    for blob, dt, arr in zip(blobs, dtypes, got):
        want = codecs.bitpack_decode(blob, dt)
        assert arr.dtype == want.dtype and arr.shape == want.shape
        np.testing.assert_array_equal(arr, want)
    groups = {
        (p["kind"], np.dtype(dt), ops._plane_bucket(p["bits"]),
         ops._ladder(p["n_pad"] // 32, 128))
        for p, dt in zip(parts, dtypes)
        if p["n"] and p["kind"] != codecs.KIND_RAW_F32
    }
    launches = [s for s in tr.spans() if s.kind == "device_launch"]
    [wait] = [s for s in tr.spans() if s.kind == "device_wait"]
    assert len(launches) == len(groups) == wait.attrs["arrays"] > 3
    crossing = sum(
        p["n"] > 0 and p["kind"] != codecs.KIND_RAW_F32 for p in parts
    )
    assert sum(s.attrs["baskets"] for s in launches) == crossing
    assert max(s.sid for s in launches) < wait.sid  # all launched, then one wait


def test_decode_ladder_pads_under_a_quarter():
    for quantum in (1, 128):
        rungs = {ops._ladder(x, quantum) for x in range(1, 64 * quantum + 1)}
        for x in range(1, 64 * quantum + 1):
            up = ops._ladder(x, quantum)
            assert up >= x and up % quantum == 0
            assert up - x < max(quantum, x / 4)
        # the number of shapes grows with log(x), not with x
        assert len(rungs) <= 8 + 4 * 3


def _per_branch_rounds(monkeypatch):
    """Make a store decode one branch per round, as the engine did
    before a round covered every branch."""
    orig = EventStore.decode_round

    def per_branch(self, blobs_by_name, tracer=None):
        return {
            n: orig(self, {n: blobs}, tracer=tracer)[n]
            for n, blobs in blobs_by_name.items()
        }

    monkeypatch.setattr(EventStore, "decode_round", per_branch)


@pytest.mark.parametrize("backend", ["device", "host"])
@pytest.mark.parametrize("capacity", [64, 0])
def test_decode_round_counts_like_per_branch_rounds(monkeypatch, backend, capacity):
    """The decode cache and tier ledgers read the same after a run with
    one round per fetch as after the same run with one round per branch,
    and so do the outputs."""
    from repro.core.engine import SkimEngine

    def run():
        st = make_nanoaod_like(
            6 * BASKET, n_hlt=8, n_filler=4, basket_events=BASKET
        )
        st.decode_backend = backend
        st.decode_cache_baskets = capacity
        eng = SkimEngine(st, chunk_events=BASKET)
        res = eng.run(QUERY)
        again = eng.run(QUERY)  # the last windows' baskets are still cached
        _assert_same_output(again, res)
        return res, st.decode_cache_stats(), st.decode_backend_stats()

    res, cache, tier = run()
    with monkeypatch.context() as m:
        _per_branch_rounds(m)
        ref, ref_cache, ref_tier = run()
    _assert_same_output(res, ref)
    assert cache == ref_cache
    assert tier == ref_tier
    if capacity:
        assert cache["hits"] > 0
    assert tier[f"{backend}_baskets"] > 0


@pytest.mark.parametrize("backend", ["device", "host"])
def test_decode_round_lru_sequence_like_per_branch_rounds(monkeypatch, backend):
    """Rounds over a small LRU that evicts within a round: every round
    hits, misses and evicts as one round per branch did, basket by
    basket, and returns the same arrays."""
    rng = np.random.default_rng(5)
    n = 4 * BASKET
    cols = {f"X_{b}": rng.integers(0, 50, n).astype(np.int32) for b in range(6)}
    cols["F"] = rng.random(n).astype(np.float32)
    names = sorted(cols)
    stores = []
    for _ in range(2):
        st = EventStore.from_arrays(cols, basket_events=BASKET, decode_backend=backend)
        st.decode_cache_baskets = 4
        stores.append(st)
    rounds = []
    for _ in range(30):
        picked = rng.choice(names, rng.integers(1, len(names) + 1), replace=False)
        rounds.append({
            str(nm): [stores[0]._blobs[nm][i] for i in sorted(rng.choice(4, 2, replace=False))]
            for nm in picked
        })
    got, want = [], []
    for r in rounds:
        got.append(stores[0].decode_round(r))
        got.append(stores[0].decode_cache_stats())
    with monkeypatch.context() as m:
        _per_branch_rounds(m)
        for r in rounds:
            want.append(stores[1].decode_round(r))
            want.append(stores[1].decode_cache_stats())
    for g, w in zip(got, want):
        if "hits" in g:
            assert g == w
            continue
        assert g.keys() == w.keys()
        for nm in g:
            for a, b in zip(g[nm], w[nm]):
                np.testing.assert_array_equal(a, b)
    assert got[-1]["hits"] > 0 and got[-1]["resident"] == 4
    assert stores[0].decode_backend_stats() == stores[1].decode_backend_stats()


def _varying_width_store(n_windows: int) -> EventStore:
    """Flat int branches whose bit widths change from window to window:
    in window ``w`` the first ``8 + 5w mod 9`` of 24 need 12 bit planes
    and the others 4, so the two plane buckets' groups take every size
    from 8 to 16 baskets within nine windows."""
    rng = np.random.default_rng(17)
    n = n_windows * BASKET
    cols = {f"B_{b:02d}": rng.random(n) < 0.2 for b in range(8)}
    wide = [8 + (5 * w) % 9 for w in range(n_windows)]
    for b in range(24):
        cols[f"I_{b:02d}"] = np.concatenate(
            [rng.integers(0, 2**10 if b < k else 2**2, BASKET) for k in wide]
        ).astype(np.int32)
    return EventStore.from_arrays(cols, basket_events=BASKET, decode_backend="device")


def test_decode_programs_stop_growing_across_windows():
    """Bucketed group shapes: after the first windows of a run whose bit
    widths vary, no window adds a decode signature to the dispatch
    ledger, although the exact group sizes keep changing."""
    n_windows = 24
    st = _varying_width_store(n_windows)
    st.decode_cache_baskets = 0
    names = sorted(st.branches)
    ops.reset_dispatch_stats()
    compiles, exact = [], set()
    for w in range(n_windows):
        blobs = {n: [blob for _, blob in st.fetch_range(n, w * BASKET, (w + 1) * BASKET)]
                 for n in names}
        got = st.decode_round(blobs)
        for n in names:
            np.testing.assert_array_equal(
                got[n][0], codecs.bitpack_decode(blobs[n][0], st.branches[n].np_dtype())
            )
        sizes = {}
        for n in names:
            p = codecs.bitpack_raw_parts(blobs[n][0])
            key = (p["kind"], ops._plane_bucket(p["bits"]))
            sizes[key] = sizes.get(key, 0) + 1
        exact |= set(sizes.items())
        compiles.append(ops.dispatch_stats()["compiles"])
    assert compiles[9] == compiles[-1]
    # without the basket-count ladder every exact size would compile
    assert compiles[-1] < len(exact)


# ---------------------------------------------------------------------------
# decode tier selection + fallback visibility
# ---------------------------------------------------------------------------


def _tiny_store(codec: str, decode_backend=None) -> EventStore:
    rng = np.random.default_rng(9)
    n = 3 * BASKET
    cols = {
        "MET_pt": (rng.exponential(30.0, n) + 1.0).astype(np.float32),
        "event": np.arange(n, dtype=np.int32),
    }
    return EventStore.from_arrays(
        cols, basket_events=BASKET, codec=codec, decode_backend=decode_backend
    )


def test_decode_backend_resolves_host_without_accelerator():
    import jax

    st = _tiny_store("bitpack")
    if jax.default_backend() == "tpu":  # pragma: no cover - TPU CI only
        assert st.resolved_decode_backend() == "device"
        return
    assert st.resolved_decode_backend() == "host"
    st.read_flat("MET_pt")
    stats = st.decode_backend_stats()
    assert stats["host_baskets"] > 0 and stats["device_baskets"] == 0


def test_forced_device_decode_is_bit_identical_on_cpu():
    dev = _tiny_store("bitpack", decode_backend="device")
    host = _tiny_store("bitpack", decode_backend="host")
    np.testing.assert_array_equal(
        dev.read_flat("MET_pt"), host.read_flat("MET_pt")
    )
    np.testing.assert_array_equal(dev.read_flat("event"), host.read_flat("event"))
    dstats = dev.decode_backend_stats()
    assert dstats["device_baskets"] > 0
    assert dstats["fallbacks"] == 0
    assert host.decode_backend_stats()["host_baskets"] > 0


def test_non_bitpack_device_request_falls_back_visibly():
    st = _tiny_store("zlib", decode_backend="device")
    ref = _tiny_store("zlib", decode_backend="host")
    np.testing.assert_array_equal(
        st.read_flat("MET_pt"), ref.read_flat("MET_pt")
    )
    stats = st.decode_backend_stats()
    assert stats["fallbacks"] > 0, stats
    assert stats["device_baskets"] == 0


def test_device_decode_failure_raises_not_falls_back(monkeypatch):
    """A broken device tier on a codec that has one is an error, never a
    quiet host decode."""
    from repro.data import store as store_mod

    def broken(*_a, **_kw):
        raise RuntimeError("device decode unavailable")

    st = _tiny_store("bitpack", decode_backend="device")
    monkeypatch.setattr(store_mod, "decode_basket_batch", broken)
    with pytest.raises(RuntimeError, match="device decode unavailable"):
        st.read_flat("MET_pt")
    stats = st.decode_backend_stats()
    assert stats["fallbacks"] == 0
    assert stats["host_baskets"] == 0 and stats["device_baskets"] == 0


def test_invalid_decode_backend_rejected():
    with pytest.raises(ValueError):
        _tiny_store("bitpack", decode_backend="gpu")


def test_batched_run_with_device_decode_bit_identical(reference):
    """End to end: batched cascade + forced device decode tier."""
    st = make_nanoaod_like(
        N_EVENTS, n_hlt=16, n_filler=8, basket_events=BASKET
    )
    st.decode_backend = "device"
    res = run_skim(
        st, QUERY, mode="near_data", pipeline=False, prune=False,
        cascade=True, device_batch=3,
    )
    _assert_same_output(res, reference)
    assert res.extras["decode_backend"] == "device"
    assert st.decode_backend_stats()["device_baskets"] > 0


@pytest.mark.parametrize("device_batch", [None, 3])
def test_pallas_backend_bit_identical_in_interpret_mode(store, reference, device_batch):
    """The Pallas fused / batched-cascade kernels (interpret mode on this
    backend) through the engine, per window and window-batched."""
    res = run_skim(
        store, QUERY, mode="near_data", pipeline=False, prune=False,
        cascade=True, device_batch=device_batch, fused_backend="pallas",
    )
    _assert_same_output(res, reference)
