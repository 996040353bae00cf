"""Pipelined/fused near-data executor vs the reference two-pass path.

Pins the tentpole contracts:

  * fused (host / xla / pallas backends) == unfused: same survivor sets,
    same output payload rows, bit-identical,
  * pipelined == serial: identical FetchStats (bytes, requests,
    per-branch accounting) — the schedule must not change the byte model,
  * the modeled double-buffered makespan never exceeds the serial sum,
  * shared-scan batch == per-query individual runs, with phase-1 byte
    amortization across overlapping tenants.
"""

import numpy as np
import pytest

from repro.core.engine import LOCAL_DISK, SkimEngine, run_skim
from repro.core.neardata import fused_window_skim, program_eval_np
from repro.core.planner import plan_skim
from repro.core.query import eval_stage, parse_query
from repro.data.store import WindowPrefetcher
from repro.data.synth import make_nanoaod_like
from tests.test_query import QUERY


@pytest.fixture(scope="module")
def store():
    return make_nanoaod_like(10_000, n_hlt=16, n_filler=8, basket_events=2048)


@pytest.fixture(scope="module")
def reference(store):
    return run_skim(store, QUERY, mode="near_data", fused=False, pipeline=False)


def _assert_same_output(res, ref):
    assert res.n_passed == ref.n_passed
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
# fused-vs-unfused equivalence
# ---------------------------------------------------------------------------


def test_fused_matches_reference_bit_identical(store, reference):
    res = run_skim(store, QUERY, mode="near_data", fused=True, pipeline=False)
    _assert_same_output(res, reference)


def test_fused_pipelined_matches_reference(store, reference):
    res = run_skim(store, QUERY, mode="near_data", fused=True, pipeline=True)
    _assert_same_output(res, reference)


def test_fused_threaded_prefetch_matches_reference(store, reference):
    res = run_skim(store, QUERY, mode="near_data", fused=True, pipeline="threads")
    _assert_same_output(res, reference)


@pytest.mark.parametrize("backend", ["host", "xla", "pallas"])
def test_fused_window_backends_agree(store, backend):
    """Every fused backend reproduces the host evaluator's mask and
    compacted payload on a decoded window."""
    q = parse_query(QUERY)
    plan = plan_skim(q, store)
    program = plan.compiled_program()
    data = {}
    for b in plan.filter_branches:
        br = store.branches[b]
        data[b] = store.read_jagged(b)[0] if br.jagged else store.read_flat(b)
    n = store.n_events

    want = np.ones(n, dtype=bool)
    for _, stage in q.stages():
        want &= eval_stage(stage, data, n)

    mask, cols = fused_window_skim(
        data, program, store,
        payload_branches=plan.payload_branches, backend=backend,
    )
    np.testing.assert_array_equal(mask, want)
    for name in plan.payload_branches:
        np.testing.assert_array_equal(cols[name], np.asarray(data[name])[want])


def test_program_interpreter_matches_staged_evaluator(store):
    """The compiled-program host interpreter == the staged AST evaluator
    on several query shapes (flat cut, trigger OR, object, HT)."""
    queries = [
        {"branches": ["MET_*"], "selection": {
            "preselection": [{"branch": "MET_pt", "op": ">", "value": 30.0}]}},
        {"branches": ["MET_*"], "selection": {
            "event": [{"type": "any",
                       "branches": ["HLT_IsoMu24", "HLT_Ele32_WPTight_Gsf"]}]}},
        {"branches": ["Jet_*"], "selection": {
            "object": [{"collection": "Jet",
                        "cuts": [{"var": "pt", "op": ">", "value": 25.0}],
                        "min_count": 2}]}},
        {"branches": ["Jet_*"], "selection": {
            "event": [{"type": "ht", "collection": "Jet", "var": "pt",
                       "object_cuts": [{"var": "pt", "op": ">", "value": 30.0}],
                       "op": ">", "value": 100.0}]}},
    ]
    for doc in queries:
        q = parse_query(doc)
        plan = plan_skim(q, store)
        data = {}
        for b in plan.filter_branches:
            br = store.branches[b]
            data[b] = store.read_jagged(b)[0] if br.jagged else store.read_flat(b)
        n = store.n_events
        want = np.ones(n, dtype=bool)
        for _, stage in q.stages():
            want &= eval_stage(stage, data, n)
        got = program_eval_np(data, plan.compiled_program(), n)
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# pipelined-vs-serial accounting invariance
# ---------------------------------------------------------------------------


def _stats_tuple(stats):
    return stats.bytes_fetched, stats.requests, dict(stats.by_branch)


@pytest.mark.parametrize("pipeline", [True, "threads"])
def test_pipelined_fetchstats_invariant(store, pipeline):
    serial = run_skim(store, QUERY, mode="near_data", fused=True, pipeline=False)
    piped = run_skim(store, QUERY, mode="near_data", fused=True, pipeline=pipeline)
    assert _stats_tuple(piped.stats) == _stats_tuple(serial.stats)


def test_pipeline_makespan_bounded(store):
    """Exact double-buffered schedule: never worse than the serial sum,
    never better than its compute component alone."""
    eng = SkimEngine(store, near_input_link=LOCAL_DISK)
    res = eng.run(QUERY, "near_data", fused=True, pipeline=True)
    serial_sum = res.breakdown.total()
    pipe = res.extras["pipeline_total"]
    # the schedule can only hide work, never invent it: bounded above by
    # the serial sum, below by the unoverlappable tail
    assert pipe <= serial_sum + 1e-9
    assert pipe >= res.breakdown.write + res.breakdown.output_transfer
    assert pipe > 0


def test_window_prefetcher_zero_event_dataset():
    """A zero-window dataset yields nothing, threaded or serial, and never
    invokes the loader."""
    calls = []
    for enabled in (False, True):
        pf = WindowPrefetcher(0, 1024, lambda s, e: calls.append((s, e)),
                              enabled=enabled)
        assert pf.windows() == []
        assert list(pf) == []
    assert calls == []


def test_window_prefetcher_single_window():
    for enabled in (False, True):
        got = list(WindowPrefetcher(100, 1024, lambda s, e: (s, e),
                                    enabled=enabled))
        assert got == [(0, 100, (0, 100))]


def test_window_prefetcher_depth_exceeds_window_count():
    """depth > #windows must not duplicate, drop, or reorder windows."""
    loads = []

    def load(start, stop):
        loads.append((start, stop))
        return start

    got = list(WindowPrefetcher(5_000, 2_000, load, depth=16, enabled=True))
    assert [(s, e) for s, e, _ in got] == [(0, 2000), (2000, 4000), (4000, 5000)]
    assert [p for _, _, p in got] == [0, 2000, 4000]
    assert sorted(loads) == [(0, 2000), (2000, 4000), (4000, 5000)]


@pytest.mark.parametrize("enabled", [False, True])
def test_window_prefetcher_worker_exception_propagates(enabled):
    """A loader crash surfaces to the consumer (not swallowed in the
    worker thread), whichever schedule runs it."""

    def load(start, stop):
        if start >= 4_000:
            raise RuntimeError("basket decode blew up")
        return start

    pf = WindowPrefetcher(10_000, 2_000, load, enabled=enabled)
    got = []
    with pytest.raises(RuntimeError, match="basket decode blew up"):
        for start, _, _ in pf:
            got.append(start)
    # the windows before the crash were delivered in order
    assert got == [0, 2000]


def test_window_prefetcher_rejects_bad_window_size():
    with pytest.raises(ValueError, match="window_events"):
        WindowPrefetcher(100, 0, lambda s, e: None)


def test_window_prefetcher_order_and_coverage():
    """The prefetcher yields every window exactly once, in order, with
    identical payloads whether threaded or serial."""
    loads: list[tuple[int, int]] = []

    def load(start, stop):
        loads.append((start, stop))
        return start * 1000 + stop

    serial = list(WindowPrefetcher(10_000, 3_000, load, enabled=False))
    loads_serial, loads[:] = list(loads), []
    threaded = list(WindowPrefetcher(10_000, 3_000, load, enabled=True))
    assert loads_serial == sorted(loads)
    assert serial == threaded
    assert [(s, e) for s, e, _ in serial] == [
        (0, 3000), (3000, 6000), (6000, 9000), (9000, 10000)
    ]
    assert [p for _, _, p in serial] == [3000, 3006000, 6009000, 9010000]


# ---------------------------------------------------------------------------
# shared-scan batch mode
# ---------------------------------------------------------------------------


def _tenant(extra: dict) -> dict:
    return {
        "branches": ["Electron_*", "Muon_*", "MET_*"],
        "selection": {
            "preselection": [{"branch": "MET_pt", "op": ">", "value": 20.0}],
            "event": [{"type": "any", "branches": ["HLT_IsoMu24"]}],
            **extra,
        },
    }


@pytest.fixture(scope="module")
def tenants():
    return [
        _tenant({"object": [{"collection": "Electron",
                             "cuts": [{"var": "pt", "op": ">", "value": 20.0}]}]}),
        _tenant({"object": [{"collection": "Muon",
                             "cuts": [{"var": "pt", "op": ">", "value": 15.0}]}]}),
        _tenant({}),
    ]


def test_shared_scan_matches_individual_runs(store, tenants):
    batch = SkimEngine(store).run_batch(tenants)
    eng = SkimEngine(store)
    assert batch.n_queries == len(tenants)
    for q, res in zip(tenants, batch.results):
        solo = eng.run(q, "near_data")
        _assert_same_output(res, solo)


def test_shared_scan_amortizes_phase1_bytes(store, tenants):
    batch = SkimEngine(store).run_batch(tenants)
    # one scan of the union must beat N scans of the parts
    assert batch.shared_stats.bytes_fetched < batch.naive_phase1_bytes
    assert batch.amortization > 1.5
    assert batch.saved_bytes == (
        batch.naive_phase1_bytes - batch.shared_stats.bytes_fetched
    )


def test_selection_free_query_all_paths(store):
    """A query with no selection (pure projection) must pass every event
    through every executor, including the fused default and shared scan."""
    q = {"branches": ["MET_*"], "selection": {}}
    ref = run_skim(store, q, mode="near_data", fused=False, pipeline=False)
    assert ref.n_passed == store.n_events
    for kw in (dict(fused=True, pipeline=False), dict(fused=True, pipeline=True),
               dict(fused=True, pipeline="threads")):
        res = run_skim(store, q, mode="near_data", **kw)
        _assert_same_output(res, ref)
    batch = SkimEngine(store).run_batch([q])
    _assert_same_output(batch.results[0], ref)


def test_invalid_pipeline_value_rejected(store):
    with pytest.raises(ValueError, match="pipeline"):
        SkimEngine(store).run(QUERY, "near_data", pipeline="bogus")


def test_shared_scan_single_query_degenerates(store):
    """A batch of one tenant behaves like the plain engine."""
    batch = SkimEngine(store).run_batch([QUERY])
    solo = SkimEngine(store).run(QUERY, "near_data")
    _assert_same_output(batch.results[0], solo)
    assert batch.amortization == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# late-growing pad_K regression
# ---------------------------------------------------------------------------


def _ragged_store_and_query(peak_window: int):
    """3-window store whose max object multiplicity (9 -> pad_K 16) first
    appears in window ``peak_window``; every other event has <= 2."""
    from repro.data.store import EventStore

    rng = np.random.default_rng(5)
    chunk, n = 256, 3 * 256
    counts = rng.integers(0, 3, n).astype(np.int32)
    lo = peak_window * chunk
    counts[lo + 7 : lo + 10] = 9
    total = int(counts.sum())
    columns = {
        "nObj": counts,
        "Obj_pt": rng.exponential(30.0, total).astype(np.float32),
        "met": rng.normal(30.0, 10.0, n).astype(np.float32),
    }
    store = EventStore.from_arrays(
        columns, jagged={"Obj_pt": "nObj"}, basket_events=chunk
    )
    query = {
        "branches": ["met", "Obj_*"],
        "selection": {
            "object": [{"collection": "Obj",
                        "cuts": [{"var": "pt", "op": ">", "value": 25.0}],
                        "min_count": 2}],
            "event": [{"type": "expr", "expr": "met + 0.1*sum(Obj_pt)",
                       "op": ">", "value": 25.0}],
        },
    }
    return store, query, chunk


@pytest.mark.parametrize("peak_window", [0, 1, 2])
def test_late_growing_pad_k_engine_bit_identical(peak_window):
    """A window late in the file with the max multiplicity must not
    mis-pad earlier or later windows, wherever the peak lands."""
    store, query, _ = _ragged_store_and_query(peak_window)
    ref = run_skim(store, query, mode="near_data", fused=False,
                   pipeline=False, prune=False)
    assert 0 < ref.n_passed < store.n_events
    res = run_skim(store, query, mode="near_data", fused=True,
                   pipeline=False, prune=False)
    _assert_same_output(res, ref)


@pytest.mark.parametrize("peak_window", [1, 2])
def test_late_growing_pad_k_device_windows(peak_window):
    """The engine's monotonic pad_K growth on the padded device backend:
    early windows evaluate at the small K, the peak window forces the
    jump, later windows run wider than they need — every mask must match
    the staged evaluator, and K must grow exactly once."""
    from repro.core.neardata import window_pad_K

    store, query, chunk = _ragged_store_and_query(peak_window)
    q = parse_query(query)
    plan = plan_skim(q, store)
    program = plan.compiled_program()
    pad_K, seen_K = 0, []
    for start in range(0, store.n_events, chunk):
        stop = min(start + chunk, store.n_events)
        data = {
            "met": store.read_flat("met", start, stop),
            "nObj": store.read_flat("nObj", start, stop),
            "Obj_pt": store.read_jagged("Obj_pt", start, stop)[0],
        }
        pad_K = max(pad_K, window_pad_K(data, program, store))
        seen_K.append(pad_K)
        mask, _ = fused_window_skim(
            data, program, store, K=pad_K, pad_to=chunk, backend="xla"
        )
        want = np.ones(stop - start, dtype=bool)
        for _, stage in q.stages():
            want &= eval_stage(stage, data, stop - start)
        np.testing.assert_array_equal(mask, want, err_msg=f"window {start}")
    # one growth step: 2 -> 16 at the peak window, stable afterwards
    assert seen_K[peak_window:] == [16] * (3 - peak_window)
    assert all(k == 2 for k in seen_K[:peak_window])
