"""Cascaded phase-1 execution: end-to-end invariants (DESIGN.md §11).

The acceptance contract of the cascade subsystem:

  * cascaded runs are **bit-identical** on survivors to the
    ``cascade=False`` preload path and to the staged ``fused=False``
    reference — across the engine (serial, modeled-pipelined, threaded),
    the shared-scan batch engine, and the cluster scatter-gather path,
    and for ANY permutation of the stage order;
  * the byte ledger is exact: ``bytes_fetched + cascade_bytes_skipped``
    equals the preload reference's fetched bytes — every basket either
    moves once or is provably skipped;
  * a branch shared by two cascade stages decodes **once per basket**
    (the decoded-basket LRU absorbs stage re-entry);
  * the canonical query form carries the cascade flag
    (``CACHE_KEY_VERSION=4``) and cached results keep hitting across
    the upgrade when semantics are unchanged.
"""

import itertools

import numpy as np
import pytest

from repro.cluster import SkimResultCache, build_cluster
from repro.cluster.cache import CACHE_KEY_VERSION, canonical_query, cache_key
from repro.core.engine import Breakdown, SkimEngine, run_skim
from repro.core.plan import CascadeExecutor, CascadeState, build_cascade
from repro.core.planner import plan_skim
from repro.core.query import eval_stage, parse_query
from repro.data.store import EventStore, FetchStats
from repro.data.synth import make_nanoaod_like

N_EVENTS = 12_000
BASKET = 2048

# multi-stage skim: a cheap selective run-range cut, an object selection,
# a trigger OR, and an event cut — enough stages for the order to matter
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
# bit-identity across executors
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pipeline", [False, True, "threads"])
@pytest.mark.parametrize("prune", [False, True])
def test_cascade_bit_identical_engine(store, reference, pipeline, prune):
    res = run_skim(
        store, QUERY, mode="near_data", fused=True, pipeline=pipeline,
        prune=prune, cascade=True,
    )
    _assert_same_output(res, reference)
    assert res.extras["cascade"]
    assert sorted(res.extras["cascade_order"]) == list(
        range(len(res.extras["cascade_order"]))
    )


def test_cascade_off_is_preload_path(store, reference):
    res = run_skim(
        store, QUERY, mode="near_data", fused=True, pipeline=False,
        prune=False, cascade=False,
    )
    _assert_same_output(res, reference)
    assert not res.extras["cascade"]
    assert res.stats.cascade_bytes_skipped == 0


def test_cascade_moves_fewer_phase1_bytes(store):
    ref = run_skim(
        store, QUERY, mode="near_data", fused=True, pipeline=False,
        prune=False, cascade=False,
    )
    res = run_skim(
        store, QUERY, mode="near_data", fused=True, pipeline=False,
        prune=False, cascade=True,
    )
    # the run-range cut kills most windows at the head of the cascade, so
    # the remaining stages never fetch them
    assert res.extras["phase1_bytes"] < ref.extras["phase1_bytes"]
    assert res.stats.cascade_bytes_skipped > 0


def test_cascade_ledger_exact_vs_preload(store):
    """Every byte either moves once or is ledgered as skipped: fetched +
    cascade_bytes_skipped == the preload reference's fetched bytes."""
    ref = run_skim(
        store, QUERY, mode="near_data", fused=True, pipeline=False,
        prune=False, cascade=False,
    )
    res = run_skim(
        store, QUERY, mode="near_data", fused=True, pipeline=False,
        prune=False, cascade=True,
    )
    assert (
        res.stats.bytes_fetched + res.stats.cascade_bytes_skipped
        == ref.stats.bytes_fetched
    )


@pytest.mark.parametrize("chunk", [256, 1024, 777])
def test_cascade_ledger_exact_multi_basket_windows(chunk):
    """The savings ledger is exact even when windows span several baskets
    (or are not basket-aligned): a filter∩output basket that dies in
    phase 1 but is re-fetched by a surviving window's phase 2 must NOT
    be credited as skipped."""
    n, basket = 16 * 256, 256
    rng = np.random.default_rng(0)
    cols = {
        # filter AND output branch, dead on alternating baskets
        "x": (
            np.where((np.arange(n) // basket) % 2 == 0, 5.0, -5.0)
            + rng.random(n)
        ).astype(np.float32),
        "h": rng.random(n).astype(np.float32),  # filter-only
        "event": np.arange(n, dtype=np.int32),
    }
    st = EventStore.from_arrays(cols, basket_events=basket)
    q = {
        "branches": ["x", "event"],
        "selection": {
            "preselection": [
                {"branch": "x", "op": ">", "value": 0.0},
                {"branch": "h", "op": ">=", "value": -1.0},
            ]
        },
    }
    kw = dict(mode="near_data", fused=True, pipeline=False, prune=False)
    eng = SkimEngine(st, chunk_events=chunk)
    ref = eng.run(q, prune=False, cascade=False)
    res = eng.run(q, prune=False, cascade=True)
    _assert_same_output(res, ref)
    assert (
        res.stats.bytes_fetched + res.stats.cascade_bytes_skipped
        == ref.stats.bytes_fetched
    ), kw


def test_pipelined_cascade_fetchstats_invariant(store):
    """Serial, modeled-pipelined, and threaded cascade runs account
    identically (the head stage is pinned; adaptation happens in window
    order on the consumer side)."""

    def tup(stats):
        return (
            stats.bytes_fetched, stats.requests, stats.cascade_bytes_skipped,
            dict(stats.by_branch),
        )

    serial = run_skim(
        store, QUERY, mode="near_data", fused=True, pipeline=False,
        cascade=True,
    )
    for pipeline in (True, "threads"):
        piped = run_skim(
            store, QUERY, mode="near_data", fused=True, pipeline=pipeline,
            cascade=True,
        )
        assert tup(piped.stats) == tup(serial.stats)
        assert piped.extras["cascade_order"] == serial.extras["cascade_order"]


def test_query_level_cascade_flag(store, reference):
    doc = dict(QUERY)
    doc["cascade"] = False
    res = run_skim(store, doc, mode="near_data")
    assert not res.extras["cascade"]
    _assert_same_output(res, reference)
    doc["cascade"] = True
    res = run_skim(store, doc, mode="near_data")
    assert res.extras["cascade"]
    _assert_same_output(res, reference)


# ---------------------------------------------------------------------------
# any stage-order permutation is bit-identical on survivors
# ---------------------------------------------------------------------------


def test_stage_order_permutations_bit_identical(store):
    q = parse_query(QUERY)
    plan = plan_skim(q, store, window_events=BASKET, cascade=True)
    n_stages = plan.cascade.n_stages
    assert n_stages == 4

    # reference mask from the staged evaluator over fully decoded data
    data = {}
    for b in plan.filter_branches:
        br = store.branches[b]
        data[b] = store.read_jagged(b)[0] if br.jagged else store.read_flat(b)
    want = np.ones(store.n_events, dtype=bool)
    for _, stage in q.stages():
        want &= eval_stage(stage, data, store.n_events)

    spans = [
        (s, min(s + BASKET, store.n_events))
        for s in range(0, store.n_events, BASKET)
    ]
    for perm in itertools.permutations(range(n_stages)):
        ex = CascadeExecutor(plan, store, order=list(perm))
        got = []
        for a, b in spans:
            out = ex.run_window(a, b, None, Breakdown(), FetchStats())
            got.append(out.mask)
        np.testing.assert_array_equal(
            np.concatenate(got), want, err_msg=f"order {perm} diverged"
        )


# ---------------------------------------------------------------------------
# adaptivity
# ---------------------------------------------------------------------------


def test_observed_selectivities_adapt_order(store):
    res = run_skim(store, QUERY, mode="near_data", cascade=True, prune=False)
    report = res.extras["cascade_stages"]
    # every executed stage carries an observed pass rate
    ran = [r for r in report if r["windows"]]
    assert ran and all(r["observed_selectivity"] is not None for r in ran)
    # the run-range head kills the tail windows, so later stages must
    # have been skipped for them
    assert any(r["windows_skipped"] > 0 for r in report)


def test_cascade_state_reorders_on_observation():
    q = parse_query(QUERY)
    store = make_nanoaod_like(4_000, n_hlt=16, basket_events=1024)
    cplan = build_cascade(q, store)
    state = CascadeState(cplan)
    head, *tail0 = state.order()
    # feed observations inverting the estimated selectivities: the most
    # accepting tail stage becomes provably useless, the least accepting
    # becomes a guaranteed killer — the tail must re-rank
    state.observe(tail0[0], 1000, 1000, 0)  # passes everything
    state.observe(tail0[-1], 1000, 0, 0)  # kills everything
    head2, *tail1 = state.order()
    assert head2 == head  # the head stays pinned for the prefetcher
    assert tail1[0] == tail0[-1]
    assert tail1[-1] == tail0[0]


def test_describe_reports_cascade_and_window_decisions(store):
    q = parse_query(QUERY)
    plan = plan_skim(q, store, window_events=BASKET, prune=True, cascade=True)
    desc = plan.describe()
    assert "cascade[4 stages:" in desc
    assert "windows[prune=" in desc and "accept_all=" in desc
    plain = plan_skim(q, store).describe()
    assert "cascade=off" in plain and "windows=unpruned" in plain


# ---------------------------------------------------------------------------
# decoded-basket LRU under cascade re-entry
# ---------------------------------------------------------------------------


def test_shared_branch_decodes_once_per_basket():
    """nElectron feeds both the preselection and the object stage (and
    phase 2): under the cascade it must decode once per basket, with
    every re-entry served from the LRU."""
    st = make_nanoaod_like(4_000, n_hlt=4, n_filler=2, basket_events=1024)
    n_baskets = st.n_baskets("MET_pt")
    q = {
        "branches": ["nElectron", "Electron_pt", "MET_pt", "event"],
        "selection": {
            "preselection": [{"branch": "nElectron", "op": ">=", "value": 0}],
            "object": [
                {
                    "collection": "Electron",
                    "cuts": [{"var": "pt", "op": ">", "value": -1.0}],
                    "min_count": 0,
                }
            ],
        },
    }
    res = run_skim(st, q, mode="near_data", prune=False, cascade=True)
    assert res.extras["cascade"]
    assert res.n_passed == st.n_events  # every window survives: no dead
    touched = set(res.plan.filter_branches) | set(res.plan.output_branches)
    stats = st.decode_cache_stats()
    # once per (branch, basket) — stage re-entry and phase 2 are hits
    assert stats["misses"] == len(touched) * n_baskets
    assert stats["hits"] >= n_baskets  # nElectron's second stage at least


# ---------------------------------------------------------------------------
# shared scan + cluster
# ---------------------------------------------------------------------------


def test_shared_scan_cascade_matches_solo(store):
    batch = SkimEngine(store, cascade=True).run_batch([QUERY, SECOND])
    for q, res in zip([QUERY, SECOND], batch.results):
        solo = run_skim(
            store, q, mode="near_data", fused=True, pipeline=False,
            prune=False, cascade=False,
        )
        _assert_same_output(res, solo)
        assert res.extras["cascade"]
    # the shared cascaded pass never pays the union preload in full
    ref = SkimEngine(store, cascade=False).run_batch([QUERY, SECOND])
    assert (
        batch.shared_stats.bytes_fetched <= ref.shared_stats.bytes_fetched
    )
    assert batch.shared_stats.cascade_bytes_skipped > 0


def test_cluster_cascade_bit_identical(store, reference):
    coord = build_cluster(store, 3, replication=False, cascade=True)
    res = coord.run(QUERY)
    _assert_same_output(res, reference)
    # the cascade can only reduce cluster bytes vs the preload nodes
    ref_nodes = build_cluster(store, 3, replication=False, cascade=False)
    assert (
        res.stats.bytes_fetched
        <= ref_nodes.run(QUERY).stats.bytes_fetched
    )


# ---------------------------------------------------------------------------
# cache key: the canonical form grew the cascade flag (v4)
# ---------------------------------------------------------------------------


def test_cache_key_version_bumped():
    assert CACHE_KEY_VERSION == 4


def test_canonical_query_carries_cascade_flag():
    base = canonical_query(QUERY)
    assert '"cascade":null' in base
    on = dict(QUERY)
    on["cascade"] = True
    off = dict(QUERY)
    off["cascade"] = False
    assert canonical_query(on) != canonical_query(off) != base
    # semantics-neutral normalizations still collapse
    assert canonical_query(dict(QUERY)) == base


def test_cache_hits_across_cascade_upgrade(store):
    """Unchanged semantics keep hitting across the v4 upgrade: the same
    query against byte-identical shards addresses identically whether
    the cluster's nodes cascade or not (the flag lives in the QUERY's
    canonical form; engine defaults don't re-address content)."""
    cache = SkimResultCache(budget_bytes=64 << 20)
    c1 = build_cluster(store, 3, replication=False, cache=cache, cascade=True)
    cold = c1.run(QUERY)
    live = 3 - len(cold.pruned_shards)
    assert cache.stats.insertions == live
    # a second cluster over re-encoded identical shards — and a different
    # node-level cascade default — keeps hitting
    cols, jag = {}, {}
    for name, br in store.branches.items():
        if br.jagged:
            jag[name] = br.counts_branch
            cols[name] = store.read_jagged(name)[0]
        else:
            cols[name] = store.read_flat(name)
    twin = EventStore.from_arrays(
        cols, jagged=jag, basket_events=store.basket_events, codec=store.codec
    )
    c2 = build_cluster(twin, 3, replication=False, cache=cache, cascade=False)
    warm = c2.run(QUERY)
    assert warm.cache_hits == live
    _assert_same_output(warm, cold)


def test_cache_key_format_includes_version(store):
    key = cache_key(QUERY, store.manifest_hash())
    assert key.startswith(f"v{CACHE_KEY_VERSION}.")
