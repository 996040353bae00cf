"""Zone-map predicate pushdown: end-to-end invariants (DESIGN.md §9).

The acceptance contract of the pruning subsystem:

  * pruned runs are **bit-identical** to the ``prune=False`` reference —
    rows, counts, output bytes — across every two-phase mode, fused and
    staged, serial and pipelined, the shared-scan batch engine, and the
    cluster scatter-gather path,
  * pruning strictly reduces fetched bytes on selective queries, with the
    savings ledgered in ``FetchStats.bytes_skipped``/``requests_skipped``
    and ``extras["pruned_windows"]``,
  * manifests carry the stats: ``manifest_hash()`` is stable across
    re-encode of identical data (the cluster cache keeps hitting across
    the stats upgrade) and changes when stats change,
  * the coordinator answers fully-pruned shards without any RPC,
  * the decoded-basket LRU dedupes phase-1/phase-2 decodes and exposes
    hit counts.
"""

import numpy as np
import pytest

from repro.cluster import SkimResultCache, build_cluster
from repro.core.engine import SkimEngine, run_skim
from repro.data.store import EventStore
from repro.data.synth import make_nanoaod_like

N_EVENTS = 12_000
BASKET = 2048

# a run-range style skim: luminosityBlock is monotone in the synthetic
# store, so most windows are provably empty; MET keeps scan windows busy
SELECTIVE = {
    "branches": ["Electron_*", "MET_*", "event", "luminosityBlock"],
    "selection": {
        "preselection": [
            {"branch": "luminosityBlock", "op": "<=", "value": 0}
        ],
        "event": [{"type": "cut", "branch": "MET_pt", "op": ">", "value": 25.0}],
    },
}

# 100% selectivity: synthetic MET_pt = exponential + 1.0 >= 1.0
ACCEPT = {
    "branches": ["MET_*", "event"],
    "selection": {
        "preselection": [{"branch": "MET_pt", "op": ">", "value": 0.5}]
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
        store, SELECTIVE, mode="near_data", fused=False, pipeline=False,
        prune=False,
    )


def _assert_same_output(res, ref):
    """rows, counts, output bytes — the bit-identity contract."""
    assert res.n_passed == ref.n_passed
    assert res.n_input == ref.n_input
    assert res.output.compressed_bytes() == ref.output.compressed_bytes()
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
# bit-identity across every executor configuration
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["client_opt", "server_side", "near_data"])
@pytest.mark.parametrize("fused", [False, True])
def test_pruned_bit_identical_all_modes(store, reference, mode, fused):
    res = run_skim(
        store, SELECTIVE, mode=mode, fused=fused, pipeline=False, prune=True
    )
    _assert_same_output(res, reference)
    assert res.extras["prune"]
    assert res.stats.bytes_skipped > 0


@pytest.mark.parametrize("pipeline", [True, "threads"])
def test_pruned_bit_identical_pipelined(store, reference, pipeline):
    res = run_skim(
        store, SELECTIVE, mode="near_data", fused=True, pipeline=pipeline,
        prune=True,
    )
    _assert_same_output(res, reference)
    # pruned windows contribute zero-load records; the modeled schedule
    # still exists and bounds below the serial sum
    assert res.extras["pipeline_total"] <= res.breakdown.total() + 1e-9


def test_accept_all_bit_identical_and_single_round(store):
    ref = run_skim(
        store, ACCEPT, mode="near_data", fused=True, pipeline=False,
        prune=False,
    )
    res = run_skim(
        store, ACCEPT, mode="near_data", fused=True, pipeline=False,
        prune=True,
    )
    _assert_same_output(res, ref)
    assert res.n_passed == store.n_events
    assert all(
        d == "accept_all" for _, _, d in res.extras["pruned_windows"]
    )
    # the output set moves exactly once: same bytes, fewer round trips
    assert res.stats.bytes_fetched == ref.stats.bytes_fetched
    assert res.stats.requests < ref.stats.requests
    assert res.breakdown.filter < ref.breakdown.filter + 1e-9


def test_prune_savings_ledger_exact_for_preload_reference(store):
    """Against the preloading (fused) reference, fetched + skipped bytes
    must account for every byte the reference moved.  ``cascade=False``
    pins the preload executor the ledger is priced against (the cascaded
    executor has its own exact ledger — tests/test_cascade.py)."""
    ref = run_skim(
        store, SELECTIVE, mode="near_data", fused=True, pipeline=False,
        prune=False, cascade=False,
    )
    res = run_skim(
        store, SELECTIVE, mode="near_data", fused=True, pipeline=False,
        prune=True, cascade=False,
    )
    assert res.stats.bytes_fetched + res.stats.bytes_skipped == (
        ref.stats.bytes_fetched
    )
    assert res.stats.requests + res.stats.requests_skipped == (
        ref.stats.requests
    )
    assert res.stats.bytes_fetched < ref.stats.bytes_fetched / 2
    pruned = [w for w in res.extras["pruned_windows"] if w[2] == "prune"]
    assert len(pruned) == len(res.extras["pruned_windows"]) > 0
    # pruned windows report zero survivors in the mergeable ledger
    rows = dict(
        ((a, b), k) for a, b, k in res.extras["window_rows"]
    )
    for a, b, _ in pruned:
        assert rows[(a, b)] == 0


def test_prune_off_is_reference(store, reference):
    res = run_skim(
        store, SELECTIVE, mode="near_data", fused=False, pipeline=False,
        prune=False,
    )
    assert res.stats.bytes_skipped == 0
    assert res.extras["pruned_windows"] == []
    assert not res.extras["prune"]
    _assert_same_output(res, reference)


# ---------------------------------------------------------------------------
# shared-scan batch engine
# ---------------------------------------------------------------------------


def test_shared_scan_pruned_matches_solo_reference(store):
    tenants = [SELECTIVE, ACCEPT]
    batch = SkimEngine(store, prune=True).run_batch(tenants)
    ref = SkimEngine(store, prune=False).run_batch(tenants)
    for res, q in zip(batch.results, tenants):
        solo = run_skim(
            store, q, mode="near_data", fused=True, pipeline=False,
            prune=False,
        )
        _assert_same_output(res, solo)
    # the ACCEPT tenant is accept-all (not prune) on the tail windows, so
    # the shared union pass stays alive for it — pruning must never trade
    # shared bytes for private re-fetches
    assert batch.shared_stats.bytes_skipped == 0
    assert batch.shared_stats.bytes_fetched == ref.shared_stats.bytes_fetched
    total = batch.shared_stats.bytes_fetched + sum(
        r.stats.bytes_fetched for r in batch.results
    )
    ref_total = ref.shared_stats.bytes_fetched + sum(
        r.stats.bytes_fetched for r in ref.results
    )
    assert total <= ref_total
    assert batch.results[0].extras["pruned_windows"]
    assert all(
        d == "accept_all"
        for _, _, d in batch.results[1].extras["pruned_windows"]
    )


def test_shared_scan_skips_union_fetch_when_no_tenant_scans(store):
    """Two selective tenants over disjoint run ranges: the tail windows
    are pruned for both, so the shared pass never fetches them."""
    t2 = {
        "branches": ["MET_*", "event", "luminosityBlock"],
        "selection": {
            "preselection": [
                {"branch": "luminosityBlock", "op": "<=", "value": 1}
            ]
        },
    }
    eng = SkimEngine(store, prune=True)
    batch = eng.run_batch([SELECTIVE, t2])
    ref = SkimEngine(store, prune=False).run_batch([SELECTIVE, t2])
    for res, refres in zip(batch.results, ref.results):
        _assert_same_output(res, refres)
    assert batch.shared_stats.bytes_skipped > 0
    assert batch.shared_stats.bytes_fetched < ref.shared_stats.bytes_fetched


# ---------------------------------------------------------------------------
# cluster: shard-level skip + bit-identity
# ---------------------------------------------------------------------------


def test_cluster_pruned_bit_identical_and_skips_shards(store, reference):
    n_windows = -(-store.n_events // BASKET)
    coord = build_cluster(store, n_windows, replication=False)
    res = coord.run(SELECTIVE)
    _assert_same_output(res, reference)
    # every shard holding only high-lumi windows is answered by the
    # coordinator from its manifest — the node never sees a request
    assert len(res.pruned_shards) == n_windows - 1
    assert res.extras["pruned_shards"] == res.pruned_shards
    assert res.extras["prune_saved_bytes"] > 0
    for node in coord.nodes:
        if node.shard.shard_id in res.pruned_shards:
            assert node.requests_served == 0


def test_cluster_prune_false_reference_path(store, reference):
    coord = build_cluster(store, 3, replication=False, prune=False)
    res = coord.run(SELECTIVE)
    _assert_same_output(res, reference)
    assert res.pruned_shards == []
    assert res.stats.bytes_skipped == 0


def test_cluster_pruned_matches_unpruned_accounting(store):
    """Pruned cluster vs pruned single node: window-aligned shards keep
    the byte/request model identical (the PR-2 contract, now with
    pruning on both sides)."""
    single = run_skim(
        store, SELECTIVE, mode="near_data", fused=True, pipeline=True,
        prune=True,
    )
    coord = build_cluster(store, 3, replication=False)
    res = coord.run(SELECTIVE)
    assert res.stats.bytes_fetched == single.stats.bytes_fetched
    assert res.stats.requests == single.stats.requests
    assert res.stats.bytes_skipped == single.stats.bytes_skipped


def test_cluster_batch_pruned_matches_solo(store):
    coord = build_cluster(store, 3, replication=False)
    batch = coord.run_batch([SELECTIVE, ACCEPT])
    for res, q in zip(batch.results, [SELECTIVE, ACCEPT]):
        solo = run_skim(
            store, q, mode="near_data", fused=True, pipeline=False,
            prune=False,
        )
        assert res.n_passed == solo.n_passed
        assert res.output.compressed_bytes() == solo.output.compressed_bytes()


# ---------------------------------------------------------------------------
# manifests, hashes, cache upgrade
# ---------------------------------------------------------------------------


def _rebuild_identical(store):
    cols, jag = {}, {}
    for name, br in store.branches.items():
        if br.jagged:
            jag[name] = br.counts_branch
            cols[name] = store.read_jagged(name)[0]
        else:
            cols[name] = store.read_flat(name)
    return EventStore.from_arrays(
        cols, jagged=jag, basket_events=store.basket_events, codec=store.codec
    )


def test_manifest_hash_stable_across_reencode(store):
    assert _rebuild_identical(store).manifest_hash() == store.manifest_hash()


def test_manifest_hash_changes_when_stats_change(store):
    other = _rebuild_identical(store)
    meta = other._baskets["MET_pt"][0]
    assert meta.vmin is not None
    meta.vmin -= 1.0  # a stats-only mutation must re-address the content
    assert other.manifest_hash() != store.manifest_hash()


def test_manifest_carries_stats_and_version(store):
    doc = store.manifest()
    assert doc["zonemap_version"] >= 1
    assert doc["integrity_version"] >= 1
    rows = doc["baskets"]["MET_pt"]
    assert all(len(r) == 9 for r in rows)
    vmin, vmax = rows[0][5], rows[0][6]
    assert vmin is not None and vmax is not None and vmin <= vmax
    # bool branches carry true-counts
    hlt = doc["baskets"]["HLT_IsoMu24"]
    assert all(isinstance(r[7], int) for r in hlt)
    # every basket row carries its CRC-32 integrity digest
    assert all(isinstance(r[8], int) for r in rows)


def test_save_load_roundtrip_preserves_stats(store, tmp_path):
    path = str(tmp_path / "st.skim")
    store.save(path)
    loaded = EventStore.load(path)
    assert loaded.manifest_hash() == store.manifest_hash()
    m0 = store._baskets["MET_pt"][0]
    m1 = loaded._baskets["MET_pt"][0]
    assert (m1.vmin, m1.vmax, m1.n_true) == (m0.vmin, m0.vmax, m0.n_true)
    # a loaded store prunes exactly like the original
    res = run_skim(loaded, SELECTIVE, mode="near_data", prune=True)
    ref = run_skim(store, SELECTIVE, mode="near_data", prune=True)
    assert res.stats.bytes_skipped == ref.stats.bytes_skipped


def test_legacy_header_without_stats_still_loads(store, tmp_path):
    """Stores written before ZONEMAP_VERSION deserialize with unknown
    stats and simply never prune."""
    import json

    path = str(tmp_path / "legacy.skim")
    store.save(path)
    with open(path, "rb") as f:
        hlen = int.from_bytes(f.read(8), "little")
        header = json.loads(f.read(hlen).decode())
        body = f.read()
    header.pop("zonemap_version")
    header["baskets"] = {
        n: [r[:5] for r in rows] for n, rows in header["baskets"].items()
    }
    hbytes = json.dumps(header).encode()
    with open(path, "wb") as f:
        f.write(len(hbytes).to_bytes(8, "little"))
        f.write(hbytes)
        f.write(body)
    legacy = EventStore.load(path)
    assert legacy._baskets["MET_pt"][0].vmin is None
    res = run_skim(legacy, SELECTIVE, mode="near_data", prune=True)
    ref = run_skim(store, SELECTIVE, mode="near_data", prune=False)
    _assert_same_output(res, ref)
    assert res.stats.bytes_skipped == 0  # nothing provable -> no pruning


def test_cluster_cache_hits_across_stats_upgrade(store):
    """The versioned manifest key: re-encoding identical data (e.g. a
    store rewritten after the stats upgrade) produces the same content
    address, so warm shards keep hitting."""
    cache = SkimResultCache(budget_bytes=64 << 20)
    c1 = build_cluster(store, 3, replication=False, cache=cache)
    cold = c1.run(SELECTIVE)
    live_shards = 3 - len(cold.pruned_shards)
    assert cache.stats.insertions == live_shards

    c2 = build_cluster(
        _rebuild_identical(store), 3, replication=False, cache=cache
    )
    warm = c2.run(SELECTIVE)
    assert warm.cache_hits == live_shards
    _assert_same_output(warm, cold)


def test_versioned_cache_key_format(store):
    from repro.cluster import cache_key
    from repro.cluster.cache import CACHE_KEY_VERSION

    key = cache_key(SELECTIVE, store.manifest_hash())
    assert key.startswith(f"v{CACHE_KEY_VERSION}.")
    assert key.endswith(store.manifest_hash())


# ---------------------------------------------------------------------------
# decoded-basket LRU
# ---------------------------------------------------------------------------


def test_decode_cache_hits_are_counted():
    st = make_nanoaod_like(4_000, n_hlt=4, n_filler=2, basket_events=1024)
    st.read_flat("MET_pt")
    misses = st.decode_cache_stats()["misses"]
    assert misses > 0
    before_hits = st.decode_cache_stats()["hits"]
    out = st.read_flat("MET_pt")
    assert st.decode_cache_stats()["hits"] > before_hits
    assert st.decode_cache_stats()["misses"] == misses
    np.testing.assert_array_equal(out, st.read_flat("MET_pt"))


def test_decode_cache_dedupes_repeat_scans():
    """Repeat queries over the same store (the multi-tenant norm) decode
    each basket once: the second run's phase 1 is all hits."""
    st = make_nanoaod_like(4_000, n_hlt=4, n_filler=2, basket_events=1024)
    st.decode_cache_baskets = 10_000  # hold everything for the assertion
    first = run_skim(st, SELECTIVE, mode="near_data", fused=True, pipeline=False)
    misses = st.decode_cache_stats()["misses"]
    second = run_skim(st, SELECTIVE, mode="near_data", fused=True, pipeline=False)
    s = st.decode_cache_stats()
    assert s["misses"] == misses  # nothing decoded twice
    assert s["hits"] > 0
    _assert_same_output(second, first)


def test_decode_cache_disabled_and_bounded():
    st = make_nanoaod_like(
        4_000, n_hlt=4, n_filler=2, basket_events=1024
    )
    st.decode_cache_baskets = 0
    a = st.read_flat("MET_pt")
    b = st.read_flat("MET_pt")
    np.testing.assert_array_equal(a, b)
    assert st.decode_cache_stats() == {
        "hits": 0, "misses": 0, "resident": 0,
        "hit_bytes": 0, "miss_bytes": 0, "saved_decode_bytes": 0,
        "hit_rate": 0.0,
    }
    st.decode_cache_baskets = 2
    st.read_flat("MET_pt")  # 4 baskets through a 2-entry cache
    assert st.decode_cache_stats()["resident"] <= 2


def test_decode_cache_entries_are_frozen():
    st = make_nanoaod_like(2_000, n_hlt=4, n_filler=2, basket_events=1024)
    blob = st.fetch_basket("MET_pt", 0)
    vals = st.decode_blob("MET_pt", blob)
    assert not vals.flags.writeable
    again = st.decode_blob("MET_pt", blob)
    assert again is vals  # served from cache, content-addressed
