import numpy as np
import pytest

from repro.core.engine import (
    LAN_100G,
    WAN_1G,
    NetworkModel,
    SkimEngine,
    run_skim,
)
from repro.data.synth import make_nanoaod_like
from tests.test_query import QUERY

MODES = ["client_plain", "client_opt", "server_side", "near_data"]


@pytest.fixture(scope="module")
def store():
    return make_nanoaod_like(12_000, n_hlt=16, n_filler=8, basket_events=2048)


@pytest.fixture(scope="module")
def results(store):
    return {m: run_skim(store, QUERY, mode=m) for m in MODES}


def test_all_modes_agree_on_selection(results):
    counts = {m: r.n_passed for m, r in results.items()}
    assert len(set(counts.values())) == 1, counts
    ref = results["client_plain"].output.read_flat("event")
    for m in MODES[1:]:
        np.testing.assert_array_equal(
            results[m].output.read_flat("event"), ref
        )


def test_outputs_identical_jagged(results):
    v0, c0 = results["client_plain"].output.read_jagged("Electron_pt")
    for m in MODES[1:]:
        v, c = results[m].output.read_jagged("Electron_pt")
        np.testing.assert_array_equal(c, c0)
        np.testing.assert_allclose(v, v0)


def test_two_phase_reduces_deserialize(results):
    """Paper Fig. 4b: Client Opt's gain is deserialize (240.4s -> 16.8s);
    basket fetch stays — every basket holding >=1 survivor still moves."""
    b_plain = results["client_plain"].breakdown
    b_opt = results["client_opt"].breakdown
    assert b_opt.deserialize < 0.2 * b_plain.deserialize


def test_two_phase_skips_empty_baskets(store):
    """With a selective-enough cut, whole baskets have no survivors and
    their output-only branches never move (byte savings appear)."""
    harsh = {
        "branches": ["Electron_*", "Jet_*", "Filler_*", "MET_*"],
        "selection": {
            "preselection": [{"branch": "MET_pt", "op": ">", "value": 250.0}]
        },
    }
    plain = run_skim(store, harsh, mode="client_plain")
    opt = run_skim(store, harsh, mode="client_opt")
    assert 0 < opt.n_passed == plain.n_passed
    assert opt.stats.bytes_fetched < 0.8 * plain.stats.bytes_fetched


def test_near_data_fastest(results):
    totals = {m: r.breakdown.total() for m, r in results.items()}
    assert totals["near_data"] < totals["client_opt"]
    assert totals["near_data"] < totals["client_plain"]
    assert totals["near_data"] < totals["server_side"]


def test_client_plain_deserialize_dominated(results):
    b = results["client_plain"].breakdown
    assert b.deserialize > b.filter  # row materialization dominates


def test_server_side_pays_per_basket_requests(results):
    # no TTreeCache locally -> requests scale with basket count
    assert results["server_side"].stats.requests > results["near_data"].stats.requests


def test_output_transfer_only_for_remote_filtering(results):
    assert results["client_plain"].breakdown.output_transfer == 0
    assert results["near_data"].breakdown.output_transfer > 0


def test_bandwidth_sensitivity(store):
    slow = SkimEngine(store, input_link=WAN_1G).run(QUERY, "client_opt")
    fast = SkimEngine(store, input_link=LAN_100G).run(QUERY, "client_opt")
    assert fast.breakdown.fetch < slow.breakdown.fetch
    assert slow.n_passed == fast.n_passed


def test_near_data_insensitive_to_client_link(store):
    # filtering happens at storage; only the small output crosses the WAN
    slow = SkimEngine(
        store, input_link=NetworkModel(0.1, rtt_s=0.05), output_link=NetworkModel(0.1)
    ).run(QUERY, "near_data")
    # input fetch stays on the PCIe-class link regardless of client tier
    assert slow.breakdown.fetch < 0.1


def test_selectivity_sane(results):
    sel = results["near_data"].selectivity
    assert 0.0 < sel < 0.2  # physics skims cut by orders of magnitude


def test_empty_selection_ok(store):
    q = dict(QUERY)
    q["selection"] = {
        "preselection": [{"branch": "MET_pt", "op": ">", "value": 1e9}]
    }
    r = run_skim(store, q, mode="near_data")
    assert r.n_passed == 0
    assert r.output.n_events == 0


# ---------------------------------------------------------------------------
# the window executor's figures, pinned: one query per phase-1 route, a
# batch of one tenant and of two.  These are what the benchmark's per-layer
# metrics read (FetchStats, and the span counters of the device tier and
# the write); the literals are the values the engine produced before the
# two window loops became one, and must not move.
# ---------------------------------------------------------------------------

PIN_EVENTS, PIN_BASKET, PIN_CHUNK = 3000, 256, 512
PIN_SPAN_ATTRS = (
    "h2d_bytes", "d2h_bytes", "pairs_live", "pair_slots",
    "baskets_cloned", "baskets_encoded",
)
PIN_QUERIES = {
    # cascaded SCAN: object cut, HT, trigger and MET stages (higgs-like)
    "higgs": QUERY,
    # a run cut every window provably passes: zone-map ACCEPT_ALL (slim-like)
    "slim": {
        "branches": ["Electron_*", "Muon_*", "MET_*", "run", "event"],
        "selection": {
            "preselection": [{"branch": "run", "op": ">=", "value": 1}]
        },
    },
    # luminosityBlock is monotone: two windows scan, the rest are pruned
    "selective": {
        "branches": ["Electron_*", "MET_*", "event", "luminosityBlock"],
        "selection": {
            "preselection": [
                {"branch": "luminosityBlock", "op": "<=", "value": 0}
            ],
            "event": [
                {"type": "cut", "branch": "MET_pt", "op": ">", "value": 25.0}
            ],
        },
    },
    "pair_mass": {
        "branches": ["Muon_*", "MET_pt", "event"],
        "selection": {"event": [
            {"type": "pair_mass", "collection": "Muon", "charge": "opposite",
             "window": [60.0, 120.0]},
        ]},
    },
    "projection": {"branches": ["MET_*", "event"], "selection": {}},
    "met": {
        "branches": ["MET_*", "event"],
        "selection": {
            "preselection": [{"branch": "MET_pt", "op": ">", "value": 21.0}]
        },
    },
}
# case -> (queries, engine options)
PIN_CASES = {
    "higgs": (["higgs"], {}),
    "higgs_batched3": (["higgs"], {"device_batch": 3}),
    "higgs_fused_no_cascade": (["higgs"], {"cascade": False}),
    "slim": (["slim"], {}),
    "selective": (["selective"], {}),
    "pair_mass": (["pair_mass"], {}),
    "pair_mass_batched3": (["pair_mass"], {"device_batch": 3}),
    "projection": (["projection"], {}),
    "two_tenants": (["higgs", "met"], {}),
    "two_tenants_batched3": (["selective", "slim"], {"device_batch": 3}),
}


def _pin_stats(stats):
    return (
        stats.bytes_fetched, stats.requests, stats.bytes_skipped,
        stats.requests_skipped, stats.cascade_bytes_skipped,
    )


def _pin_figures(case):
    """Run one pinned case on the device tier (jitted decode, XLA fused
    backend) and return its figures."""
    from repro.obs.trace import Tracer

    names, options = PIN_CASES[case]
    store = make_nanoaod_like(
        PIN_EVENTS, n_hlt=8, n_filler=2, basket_events=PIN_BASKET
    )
    store.decode_backend = "device"
    engine_kw = dict(chunk_events=PIN_CHUNK, fused_backend="xla", **options)
    tracer = Tracer()
    queries = [PIN_QUERIES[name] for name in names]
    if len(queries) == 1:
        results = [SkimEngine(store, **engine_kw).run(queries[0], tracer=tracer)]
        shared = None
    else:
        batch = SkimEngine(store, **engine_kw).run_batch(
            queries, tracer=tracer
        )
        results, shared = batch.results, _pin_stats(batch.shared_stats)
    spans: dict[str, list] = {}
    for span in tracer.spans():
        row = spans.setdefault(span.kind, [0] * (1 + len(PIN_SPAN_ATTRS)))
        row[0] += 1
        for j, attr in enumerate(PIN_SPAN_ATTRS):
            row[j + 1] += int(span.attrs.get(attr, 0))
    grid = [
        (a, min(a + PIN_CHUNK, PIN_EVENTS)) for a in range(0, PIN_EVENTS, PIN_CHUNK)
    ]
    for r in results:
        assert [(a, b) for a, b, _ in r.extras["window_rows"]] == grid
    return {
        "results": [
            (
                r.output.manifest_hash()[:16], r.n_passed,
                [k for _, _, k in r.extras["window_rows"]],
                _pin_stats(r.stats),
            )
            for r in results
        ],
        "shared": shared,
        "spans": {kind: tuple(row) for kind, row in sorted(spans.items())},
    }


PIN_EXPECTED = {
    "higgs": {
        "results": [
            ("f9702633bbf9476b", 19, [1, 0, 2, 4, 10, 2], (305664, 35, 0, 0, 0)),
        ],
        "shared": None,
        "spans": {
            "cascade_stage": (30, 0, 0, 0, 0, 0, 0),
            "decode_device": (35, 0, 0, 0, 0, 0, 0),
            "decode_prep": (70, 0, 0, 0, 0, 0, 0),
            "device_launch": (58, 1606608, 0, 0, 0, 0, 0),
            "device_wait": (53, 0, 1863800, 0, 0, 0, 0),
            "fetch": (35, 0, 0, 0, 0, 0, 0),
            "load_window": (6, 0, 0, 0, 0, 0, 0),
            "phase2": (5, 0, 0, 0, 0, 0, 0),
            "plan": (1, 0, 0, 0, 0, 0, 0),
            "query": (1, 0, 0, 0, 0, 0, 0),
            "stage_inputs": (30, 0, 0, 0, 0, 0, 0),
            "window": (6, 0, 0, 0, 0, 0, 0),
            "write": (1, 0, 0, 0, 0, 0, 33),
        },
    },
    "higgs_batched3": {
        "results": [
            ("f9702633bbf9476b", 19, [1, 0, 2, 4, 10, 2], (305664, 35, 0, 0, 0)),
        ],
        "shared": None,
        "spans": {
            "cascade_stage": (10, 0, 0, 0, 0, 0, 0),
            "decode_device": (35, 0, 0, 0, 0, 0, 0),
            "decode_prep": (70, 0, 0, 0, 0, 0, 0),
            "device_batch": (2, 0, 0, 0, 0, 0, 0),
            "device_launch": (40, 1664000, 0, 0, 0, 0, 0),
            "device_wait": (35, 0, 1737688, 0, 0, 0, 0),
            "fetch": (35, 0, 0, 0, 0, 0, 0),
            "load_window": (6, 0, 0, 0, 0, 0, 0),
            "phase2": (5, 0, 0, 0, 0, 0, 0),
            "plan": (1, 0, 0, 0, 0, 0, 0),
            "query": (1, 0, 0, 0, 0, 0, 0),
            "stage_inputs": (12, 0, 0, 0, 0, 0, 0),
            "window": (6, 0, 0, 0, 0, 0, 0),
            "write": (1, 0, 0, 0, 0, 0, 33),
        },
    },
    "higgs_fused_no_cascade": {
        "results": [
            ("f9702633bbf9476b", 19, [1, 0, 2, 4, 10, 2], (305664, 11, 0, 0, 0)),
        ],
        "shared": None,
        "spans": {
            "decode_device": (11, 0, 0, 0, 0, 0, 0),
            "decode_prep": (22, 0, 0, 0, 0, 0, 0),
            "device_launch": (28, 3768400, 0, 0, 0, 0, 0),
            "device_wait": (17, 0, 1826840, 0, 0, 0, 0),
            "fetch": (11, 0, 0, 0, 0, 0, 0),
            "kernel": (6, 0, 0, 0, 0, 0, 0),
            "load_window": (6, 0, 0, 0, 0, 0, 0),
            "phase2": (5, 0, 0, 0, 0, 0, 0),
            "plan": (1, 0, 0, 0, 0, 0, 0),
            "query": (1, 0, 0, 0, 0, 0, 0),
            "stage_inputs": (6, 0, 0, 0, 0, 0, 0),
            "window": (6, 0, 0, 0, 0, 0, 0),
            "write": (1, 0, 0, 0, 0, 0, 33),
        },
    },
    "pair_mass": {
        "results": [
            ("4d9017b1aab57866", 43, [10, 5, 10, 3, 7, 8], (42104, 12, 0, 0, 0)),
        ],
        "shared": None,
        "spans": {
            "cascade_stage": (6, 0, 0, 0, 0, 0, 0),
            "decode_device": (12, 0, 0, 0, 0, 0, 0),
            "decode_prep": (24, 0, 0, 0, 0, 0, 0),
            "device_launch": (24, 544800, 0, 365, 18432, 0, 0),
            "device_wait": (18, 0, 651288, 0, 0, 0, 0),
            "fetch": (12, 0, 0, 0, 0, 0, 0),
            "load_window": (6, 0, 0, 0, 0, 0, 0),
            "phase2": (6, 0, 0, 0, 0, 0, 0),
            "plan": (1, 0, 0, 0, 0, 0, 0),
            "query": (1, 0, 0, 0, 0, 0, 0),
            "stage_inputs": (6, 0, 0, 0, 0, 0, 0),
            "window": (6, 0, 0, 0, 0, 0, 0),
            "write": (1, 0, 0, 0, 0, 0, 9),
        },
    },
    "pair_mass_batched3": {
        "results": [
            ("4d9017b1aab57866", 43, [10, 5, 10, 3, 7, 8], (42104, 12, 0, 0, 0)),
        ],
        "shared": None,
        "spans": {
            "cascade_stage": (2, 0, 0, 0, 0, 0, 0),
            "decode_device": (12, 0, 0, 0, 0, 0, 0),
            "decode_prep": (24, 0, 0, 0, 0, 0, 0),
            "device_batch": (2, 0, 0, 0, 0, 0, 0),
            "device_launch": (22, 553536, 0, 365, 18432, 0, 0),
            "device_wait": (16, 0, 639480, 0, 0, 0, 0),
            "fetch": (12, 0, 0, 0, 0, 0, 0),
            "load_window": (6, 0, 0, 0, 0, 0, 0),
            "phase2": (6, 0, 0, 0, 0, 0, 0),
            "plan": (1, 0, 0, 0, 0, 0, 0),
            "query": (1, 0, 0, 0, 0, 0, 0),
            "stage_inputs": (4, 0, 0, 0, 0, 0, 0),
            "window": (6, 0, 0, 0, 0, 0, 0),
            "write": (1, 0, 0, 0, 0, 0, 9),
        },
    },
    "projection": {
        "results": [
            ("bdfcab6e92fbb991", 3000, [512, 512, 512, 512, 512, 440], (25616, 6, 0, 0, 0)),
        ],
        "shared": None,
        "spans": {
            "decode_device": (12, 0, 0, 0, 0, 0, 0),
            "decode_prep": (12, 0, 0, 0, 0, 0, 0),
            "device_launch": (6, 49200, 0, 0, 0, 0, 0),
            "device_wait": (6, 0, 196608, 0, 0, 0, 0),
            "fetch": (12, 0, 0, 0, 0, 0, 0),
            "load_window": (6, 0, 0, 0, 0, 0, 0),
            "phase2": (6, 0, 0, 0, 0, 0, 0),
            "plan": (1, 0, 0, 0, 0, 0, 0),
            "query": (1, 0, 0, 0, 0, 0, 0),
            "window": (6, 0, 0, 0, 0, 0, 0),
            "write": (1, 0, 0, 0, 0, 36, 0),
        },
    },
    "selective": {
        "results": [
            ("e52160778305b194", 459, [232, 227, 0, 0, 0, 0], (17456, 6, 8568, 4, 0)),
        ],
        "shared": None,
        "spans": {
            "cascade_stage": (4, 0, 0, 0, 0, 0, 0),
            "decode_device": (6, 0, 0, 0, 0, 0, 0),
            "decode_prep": (12, 0, 0, 0, 0, 0, 0),
            "device_launch": (10, 110668, 0, 0, 0, 0, 0),
            "device_wait": (8, 0, 270352, 0, 0, 0, 0),
            "fetch": (6, 0, 0, 0, 0, 0, 0),
            "load_window": (2, 0, 0, 0, 0, 0, 0),
            "phase2": (2, 0, 0, 0, 0, 0, 0),
            "plan": (1, 0, 0, 0, 0, 0, 0),
            "query": (1, 0, 0, 0, 0, 0, 0),
            "stage_inputs": (4, 0, 0, 0, 0, 0, 0),
            "window": (6, 0, 0, 0, 0, 0, 0),
            "write": (1, 0, 0, 0, 0, 0, 22),
        },
    },
    "slim": {
        "results": [
            ("a84c34a3798223a8", 3000, [512, 512, 512, 512, 512, 440], (78008, 6, 0, 0, 0)),
        ],
        "shared": None,
        "spans": {
            "decode_device": (12, 0, 0, 0, 0, 0, 0),
            "decode_prep": (12, 0, 0, 0, 0, 0, 0),
            "device_launch": (12, 360800, 0, 0, 0, 0, 0),
            "device_wait": (6, 0, 1146880, 0, 0, 0, 0),
            "fetch": (12, 0, 0, 0, 0, 0, 0),
            "load_window": (6, 0, 0, 0, 0, 0, 0),
            "phase2": (6, 0, 0, 0, 0, 0, 0),
            "plan": (1, 0, 0, 0, 0, 0, 0),
            "query": (1, 0, 0, 0, 0, 0, 0),
            "window": (6, 0, 0, 0, 0, 0, 0),
            "write": (1, 0, 0, 0, 0, 216, 0),
        },
    },
    "two_tenants": {
        "results": [
            ("f9702633bbf9476b", 19, [1, 0, 2, 4, 10, 2], (230688, 5, 0, 0, 0)),
            ("f6f8e15cbd075e3e", 1522, [254, 273, 259, 263, 256, 217], (2272, 1, 0, 0, 0)),
        ],
        "shared": (74976, 24, 0, 0, 0),
        "spans": {
            "cascade_stage": (36, 0, 0, 0, 0, 0, 0),
            "decode_device": (41, 0, 0, 0, 0, 0, 0),
            "decode_prep": (60, 0, 0, 0, 0, 0, 0),
            "device_launch": (65, 1671008, 0, 0, 0, 0, 0),
            "device_wait": (60, 0, 1941648, 0, 0, 0, 0),
            "fetch": (41, 0, 0, 0, 0, 0, 0),
            "load_window": (6, 0, 0, 0, 0, 0, 0),
            "phase2": (11, 0, 0, 0, 0, 0, 0),
            "plan": (1, 0, 0, 0, 0, 0, 0),
            "query": (1, 0, 0, 0, 0, 0, 0),
            "stage_inputs": (36, 0, 0, 0, 0, 0, 0),
            "window": (6, 0, 0, 0, 0, 0, 0),
            "write": (2, 0, 0, 0, 0, 0, 51),
        },
    },
    "two_tenants_batched3": {
        "results": [
            ("e52160778305b194", 459, [232, 227, 0, 0, 0, 0], (13008, 2, 0, 0, 0)),
            ("a84c34a3798223a8", 3000, [512, 512, 512, 512, 512, 440], (60144, 6, 0, 0, 0)),
        ],
        "shared": (5584, 8, 0, 0, 0),
        "spans": {
            "cascade_stage": (2, 0, 0, 0, 0, 0, 0),
            "decode_device": (16, 0, 0, 0, 0, 0, 0),
            "decode_prep": (32, 0, 0, 0, 0, 0, 0),
            "device_batch": (1, 0, 0, 0, 0, 0, 0),
            "device_launch": (25, 449100, 0, 0, 0, 0, 0),
            "device_wait": (17, 0, 1327416, 0, 0, 0, 0),
            "fetch": (16, 0, 0, 0, 0, 0, 0),
            "load_window": (6, 0, 0, 0, 0, 0, 0),
            "phase2": (8, 0, 0, 0, 0, 0, 0),
            "plan": (1, 0, 0, 0, 0, 0, 0),
            "query": (1, 0, 0, 0, 0, 0, 0),
            "stage_inputs": (3, 0, 0, 0, 0, 0, 0),
            "window": (6, 0, 0, 0, 0, 0, 0),
            "write": (2, 0, 0, 0, 0, 216, 22),
        },
    },
}


@pytest.mark.parametrize("case", sorted(PIN_CASES))
def test_window_executor_figures_pinned(case):
    assert _pin_figures(case) == PIN_EXPECTED[case]
