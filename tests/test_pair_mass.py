"""All-pairs mass window (``pair_mass`` node, GROUP_PAIR_MASS; DESIGN.md §17).

The ADL benchmark's Query 5 keeps events with an opposite-charge muon
pair, taken from all pairs, of invariant mass in (60, 120) GeV.  Pinned
here, on seeded jagged data on the CPU:

  * the float64 host rule (``expr.all_pair_masses``) against a per-event
    brute-force loop;
  * the jnp group (``kernels/ref.py``) against the host rule, and the
    Pallas ``predicate_eval`` / ``skim_fused`` kernels and their batched
    forms (interpret mode) against staged ``eval_node``;
  * edge cases: 0 or 1 objects, one charge only, every slot pair at
    K = 8 and 16 (cyclic roll steps, wrap included), masses on and next
    to each window edge;
  * the served path: ``SkimService(EngineBackend(store))`` on the device
    tier against the staged float64 reference;
  * zone maps, the cache's canonical form, planning, the span counters
    and parse errors.
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.verify import VerifyError, verify_program
from repro.cluster.cache import canonical_query
from repro.core import expr as xpr
from repro.core.engine import SkimEngine, run_skim
from repro.core.neardata import (
    build_padded_inputs,
    fused_window_skim,
    pair_work,
    program_eval_np,
    window_pad_K,
)
from repro.core.plan import build_cascade, stage_kind
from repro.core.query import PairMassWindow, eval_node, parse_query
from repro.core.zonemap import MAYBE, SCAN, classify_node, classify_windows
from repro.data.store import EventStore
from repro.kernels import ops, ref
from repro.kernels import predicate_eval as pe
from repro.kernels import skim_fused as sf
from repro.kernels.predicate_eval import Group, compile_query
from repro.obs.trace import Tracer
from repro.serve.service import EngineBackend, SkimService

VARS = ("pt", "eta", "phi", "mass", "charge")
CHARGES = ("opposite", "same", "any")
WINDOW = (60.0, 120.0)


def q5(charge="opposite", window=WINDOW, branches=("MET_pt", "event")):
    return {
        "branches": list(branches),
        "selection": {"event": [
            {"type": "pair_mass", "collection": "Muon", "charge": charge,
             "window": list(window)},
        ]},
    }


def muons(n, seed, mean=2.0, floor=1, force=()):
    """Seeded jagged muons: ``floor + Poisson(mean - floor)`` per event,
    ``force`` maps event -> a multiplicity set by hand."""
    rng = np.random.default_rng(seed)
    counts = (floor + rng.poisson(mean - floor, n)).astype(np.int32)
    for e, c in dict(force).items():
        counts[e] = c
    tot = int(counts.sum())
    data = {
        "nMuon": counts,
        "Muon_pt": (rng.exponential(25.0, tot) + 3.0).astype(np.float32),
        "Muon_eta": rng.uniform(-2.4, 2.4, tot).astype(np.float32),
        "Muon_phi": rng.uniform(-np.pi, np.pi, tot).astype(np.float32),
        "Muon_mass": np.full(tot, 0.10566, np.float32),
        "Muon_charge": rng.choice(np.array([-1, 1], np.int32), tot),
    }
    return data


def textbook_mass(p, q):
    """Float64 four-vector mass, E² − |p|², of two (pt, eta, phi, m)."""
    def four(pt, eta, phi, m):
        return (math.sqrt((pt * math.cosh(eta)) ** 2 + m * m),
                pt * math.cos(phi), pt * math.sin(phi), pt * math.sinh(eta))

    e1, x1, y1, z1 = four(*p)
    e2, x2, y2, z2 = four(*q)
    m2 = (e1 + e2) ** 2 - ((x1 + x2) ** 2 + (y1 + y2) ** 2 + (z1 + z2) ** 2)
    return math.sqrt(max(m2, 0.0))


def brute_force(data, charge, lo, hi):
    """Per-event loop over every pair i < j -> (mask, margin)."""
    counts = data["nMuon"]
    off = np.concatenate([[0], np.cumsum(counts)])
    cond = {"opposite": lambda qq: qq < 0, "same": lambda qq: qq > 0,
            "any": lambda qq: True}[charge]
    mask = np.zeros(len(counts), dtype=bool)
    margin = np.full(len(counts), np.inf)
    for e in range(len(counts)):
        objs = [tuple(float(data[f"Muon_{v}"][k]) for v in VARS)
                for k in range(off[e], off[e + 1])]
        for j in range(len(objs)):
            for i in range(j):
                if not cond(objs[i][4] * objs[j][4]):
                    continue
                m = textbook_mass(objs[i][:4], objs[j][:4])
                mask[e] |= lo < m < hi
                margin[e] = min(margin[e], abs(m - lo) / lo, abs(m - hi) / hi)
    return mask, margin


def padded(data, prog, K):
    store = EventStore.from_arrays(
        {**data, "MET_pt": np.ones(len(data["nMuon"]), np.float32)},
        jagged={f"Muon_{v}": "nMuon" for v in VARS}, basket_events=4096,
    )
    pb = build_padded_inputs(data, prog, store, K=K, to_device=False)
    return pb.terms, pb.valid, pb.weights


# ---------------------------------------------------------------------------
# the float64 host rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_pair_indices_enumerate_every_pair_once(seed):
    counts = muons(3_000, seed, force={5: 0, 6: 1, 7: 16, 8: 23})["nMuon"]
    ev, a, b = xpr.pair_indices(counts)
    off = np.concatenate([[0], np.cumsum(counts)])
    want = [(e, off[e] + i, off[e] + j)
            for e in range(len(counts)) for j in range(counts[e]) for i in range(j)]
    np.testing.assert_array_equal(np.stack([ev, a, b], 1), np.array(want))


@pytest.mark.parametrize("charge", CHARGES)
@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_host_rule_matches_brute_force(charge, seed):
    data = muons(2_000, seed, force={0: 0, 1: 1, 2: 11})
    want, margin = brute_force(data, charge, *WINDOW)
    node = parse_query(q5(charge)).event_stage[0]
    got = eval_node(node, data, 2_000)
    # float64 textbook against the cancellation-free form: ~1e-15 apart
    differ = got != want
    assert (margin[differ] < 1e-9).all()
    assert 0 < want.sum() < 2_000 and not got[:2].any()


@pytest.mark.parametrize("charge", CHARGES)
def test_compiled_host_interpreter_matches_staged(charge):
    data = muons(4_000, 11)
    q = parse_query(q5(charge))
    np.testing.assert_array_equal(
        program_eval_np(data, compile_query(q), 4_000),
        eval_node(q.event_stage[0], data, 4_000),
    )


# ---------------------------------------------------------------------------
# the jnp group and the Pallas kernels
# ---------------------------------------------------------------------------


def _flips_within_margin(got, want, data, charge, tol=1e-5):
    """Events the float32 side decides differently lie within ``tol`` of
    a window edge (the contract of DESIGN.md §17)."""
    differ = np.flatnonzero(got != want)
    if len(differ):
        ev, m, qq = xpr.all_pair_masses(data, "Muon")
        ok = {"opposite": qq < 0, "same": qq > 0, "any": qq == qq}[charge]
        dist = np.minimum(np.abs(m - WINDOW[0]) / WINDOW[0],
                          np.abs(m - WINDOW[1]) / WINDOW[1])
        near = np.zeros(len(got), dtype=bool)
        near[ev[ok & (dist < tol)]] = True
        assert near[differ].all()
    return len(differ)


@pytest.mark.parametrize("charge", CHARGES)
@pytest.mark.parametrize("K", [8, 16])
def test_ref_group_matches_host_rule(charge, K):
    force = {3: 9} if K == 16 else {}
    data = muons(6_000, 21 + K, force=force)
    data["nMuon"] = np.minimum(data["nMuon"], K)  # nothing overflows K
    data = {k: (v if k == "nMuon" else v[: data["nMuon"].sum()]) for k, v in data.items()}
    q = parse_query(q5(charge))
    prog = compile_query(q)
    assert window_pad_K(data, prog, _store_of(data)) <= K
    want = eval_node(q.event_stage[0], data, 6_000)
    got = np.asarray(ref.predicate_eval_ref(*map(jnp.asarray, padded(data, prog, K)), prog))
    assert _flips_within_margin(got, want, data, charge) == 0
    assert 0 < want.sum()


def _store_of(data):
    return EventStore.from_arrays(
        {**data, "MET_pt": np.ones(len(data["nMuon"]), np.float32)},
        jagged={f"Muon_{v}": "nMuon" for v in VARS}, basket_events=4096,
    )


KERNELS = ["predicate_eval", "predicate_eval_batch", "skim_fused", "skim_fused_batch"]


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("K", [8, 16])
def test_pallas_kernels_match_staged(kernel, K):
    E = 1024
    data = muons(E, 40 + K, force={E - 1: K} if K == 16 else {7: 8})
    q = parse_query(q5())
    prog = compile_query(q)
    want = eval_node(q.event_stage[0], data, E)
    terms, valid, weights = padded(data, prog, K)
    payload = np.arange(E, dtype=np.float32)[:, None]
    if kernel == "predicate_eval":
        got = np.asarray(pe.predicate_eval(
            jnp.asarray(terms), jnp.asarray(valid), jnp.asarray(weights),
            program=prog, interpret=True, event_tile=512)) > 0
    elif kernel == "predicate_eval_batch":
        b = [jnp.asarray(np.stack([x, x])) for x in (terms, valid, weights)]
        out = np.asarray(pe.predicate_eval_batch(*b, program=prog, interpret=True,
                                                 event_tile=512)) > 0
        np.testing.assert_array_equal(out[0], out[1])
        got = out[0]
    elif kernel == "skim_fused":
        packed, count = ops.skim_fused(terms, valid, weights, payload, prog, interpret=True)
        got = np.zeros(E, dtype=bool)
        got[np.asarray(packed)[: int(count), 0].astype(int)] = True
    else:
        b = [np.stack([x, x]) for x in (terms, valid, weights, payload)]
        packed, counts = ops.fused_skim_batch(*b, prog, use_pallas=True)
        rows = np.asarray(packed)
        got = np.zeros(E, dtype=bool)
        got[rows[1, : int(counts[1]), 0].astype(int)] = True
    assert _flips_within_margin(got, want, data, "opposite") == 0
    assert 0 < want.sum() < E


@pytest.mark.parametrize("K", [8, 16])
def test_every_slot_pair_is_found(K):
    """One event row per slot pair (i, j): only that pair is two objects
    back to back at 40 GeV each (m = 80); every other object is soft and
    collinear with j, so no other pair reaches the window.  Both the jnp
    group and the kernel find exactly the pair of each row, at every
    cyclic distance, wrap included."""
    cases = [(i, j) for j in range(K) for i in range(j)]
    E = 512 * -(-len(cases) // 512)
    pt = np.ones((E, K), np.float32)
    phi = np.zeros((E, K), np.float32)
    for row, (i, j) in enumerate(cases):
        pt[row, [i, j]] = 40.0
        phi[row, i] = np.float32(np.pi)
    terms = np.stack([pt, np.zeros_like(pt), phi, np.zeros_like(pt), np.ones_like(pt)])
    valid = np.zeros((1, E, K), np.float32)
    valid[:, : len(cases)] = 1.0
    prog = compile_query(parse_query(q5("any")))
    args = (jnp.asarray(terms), jnp.asarray(valid), jnp.asarray(np.zeros_like(valid)))
    want = np.arange(E) < len(cases)
    np.testing.assert_array_equal(np.asarray(ref.predicate_eval_ref(*args, prog)), want)
    got = pe.predicate_eval(*args, program=prog, interpret=True, event_tile=512)
    np.testing.assert_array_equal(np.asarray(got) > 0, want)
    # the same rows with the light pair moved off the window: nothing passes
    terms[0][terms[0] == 40.0] = 20.0  # m = 40
    args = (jnp.asarray(terms),) + args[1:]
    assert not np.asarray(ref.predicate_eval_ref(*args, prog)).any()


def edge_events():
    """One pair of objects at rest per event, m = m1 + m2 exactly in
    float32 and float64: on each edge (60, 120), just inside, just
    outside; then an event of one object, one with none, and one whose
    only in-window pair has equal charges."""
    masses = [30.0, 60.0, 30.5, 59.5, 29.5, 60.5]
    counts = [2] * len(masses) + [1, 0, 2]
    mass = sum(([m, m] for m in masses), []) + [45.0] + [45.0, 45.0]
    charge = [1, -1] * len(masses) + [1] + [1, 1]
    n = len(mass)
    return {
        "nMuon": np.array(counts, np.int32),
        "Muon_pt": np.zeros(n, np.float32),
        "Muon_eta": np.zeros(n, np.float32),
        "Muon_phi": np.zeros(n, np.float32),
        "Muon_mass": np.array(mass, np.float32),
        "Muon_charge": np.array(charge, np.int32),
    }


EDGE_WANT = {
    "opposite": [False, False, True, True, False, False, False, False, False],
    "same": [False] * 8 + [True],
    "any": [False, False, True, True, False, False, False, False, True],
}


@pytest.mark.parametrize("charge", CHARGES)
@pytest.mark.parametrize("path", ["staged", "host", "jnp", "pallas"])
def test_window_edges_are_open(charge, path):
    data = edge_events()
    q = parse_query(q5(charge))
    n = len(data["nMuon"])
    if path == "staged":
        got = eval_node(q.event_stage[0], data, n)
    elif path == "host":
        got = program_eval_np(data, compile_query(q), n)
    else:
        prog = compile_query(q)
        terms, valid, weights = (np.pad(x, ((0, 0), (0, 512 - n), (0, 0)))
                                 for x in padded(data, prog, 8))
        args = [jnp.asarray(x) for x in (terms, valid, weights)]
        if path == "jnp":
            got = np.asarray(ref.predicate_eval_ref(*args, prog))[:n]
        else:
            got = np.asarray(pe.predicate_eval(*args, program=prog, interpret=True,
                                               event_tile=512))[:n] > 0
    assert list(got) == EDGE_WANT[charge]


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------


def q5_store(n=12_288, seed=2**31 + 99, basket_events=2048):
    data = muons(n, seed, force={100: 9, 5_000: 12})
    rng = np.random.default_rng(seed + 1)
    cols = {
        **data,
        "MET_pt": (rng.exponential(25.0, n) + 1.0).astype(np.float32),
        "event": np.arange(n, dtype=np.int32),
        "run": np.full(n, 194_533, np.int32),
    }
    return EventStore.from_arrays(
        cols, jagged={f"Muon_{v}": "nMuon" for v in VARS},
        basket_events=basket_events, decode_backend="device",
    ), data


@pytest.fixture(scope="module")
def served():
    store, data = q5_store()
    ref_res = run_skim(store, q5(), fused=False, pipeline=False, prune=False)
    return store, data, ref_res


@pytest.mark.parametrize("device_batch", [None, 3])
def test_service_device_tier_matches_staged_reference(served, device_batch):
    store, data, ref_res = served
    svc = SkimService(EngineBackend(store, fused_backend="pallas",
                                    device_batch=device_batch))
    job = svc.submit(q5())
    svc.run_until_idle()
    assert job.state == "DONE", job.error
    assert store.decode_backend_stats()["fallbacks"] == 0
    got, want = job.result.output, ref_res.output
    assert job.result.n_passed == ref_res.n_passed > 0
    for name in ("event", "MET_pt"):
        np.testing.assert_array_equal(got.read_flat(name), want.read_flat(name))
    for name in ("Muon_pt", "Muon_charge"):
        v1, c1 = got.read_jagged(name)
        v0, c0 = want.read_jagged(name)
        np.testing.assert_array_equal(c1, c0)
        np.testing.assert_array_equal(v1, v0)


def test_span_counters_count_slot_and_live_pairs(served):
    store, data, _ = served
    prog = compile_query(parse_query(q5()))
    tr = Tracer()
    lo, hi = 2048, 4096
    window = {k: (v[lo:hi] if k == "nMuon" else
                  v[data["nMuon"][:lo].sum():data["nMuon"][:hi].sum()])
              for k, v in data.items()}
    window["MET_pt"] = np.ones(hi - lo, np.float32)
    with tr.span("w", kind="window"):
        fused_window_skim(window, prog, store, backend="xla", tracer=tr)
    (launch,) = [s for s in tr.spans() if s.kind == "device_launch"]
    n = window["nMuon"].astype(np.int64)
    K = 1 << int(n.max() - 1).bit_length()
    assert launch.attrs["pairs_live"] == int((n * (n - 1) // 2).sum())
    assert launch.attrs["pair_slots"] == 2048 * K * (K - 1) // 2


def test_batched_cascade_counts_pairs_on_its_launch(served):
    store, _, _ = served
    tr = Tracer()
    SkimEngine(store, fused_backend="xla", device_batch=2).run(q5(), tracer=tr)
    launches = [s for s in tr.spans() if s.kind == "device_launch"
                and s.attrs.get("op") == "cascade_stage" and "pair_slots" in s.attrs]
    assert launches
    counts = store.read_flat("nMuon").astype(np.int64)
    assert sum(s.attrs["pairs_live"] for s in launches) == int((counts * (counts - 1) // 2).sum())
    staged = {s.parent: s.attrs for s in tr.spans() if s.kind == "stage_inputs" and "K" in s.attrs}
    for s in launches:
        K = staged[s.parent]["K"]
        assert s.attrs["pair_slots"] == staged[s.parent]["events"] * K * (K - 1) // 2


def test_pair_work_is_silent_without_pair_groups():
    prog = compile_query(parse_query({"selection": {"event": [
        {"type": "cut", "branch": "MET_pt", "op": ">", "value": 1.0}]}}))
    assert pair_work(prog, lambda name: np.ones(4), 4, 8) == {}


# ---------------------------------------------------------------------------
# zone maps, cache, planning, verification, parsing
# ---------------------------------------------------------------------------


def test_zone_maps_never_skip_a_pair_window(served):
    store, _, _ = served
    q = parse_query(q5())
    node = q.event_stage[0]
    assert classify_node(node, lambda b: store.window_stats(b, 0, store.n_events), store) == MAYBE
    spans = [(a, a + 2048) for a in range(0, store.n_events, 2048)]
    assert set(classify_windows(q, store, spans)) == {SCAN}


@pytest.mark.parametrize("charge", CHARGES)
def test_cache_canonical_form(charge):
    doc = canonical_query(q5(charge))
    assert f'["pair_mass","Muon","{charge}",60.0,120.0]' in doc
    assert canonical_query(q5(charge, window=(60, 120.0))) == doc
    others = {canonical_query(q5(c)) for c in CHARGES if c != charge}
    assert doc not in others


def test_plan_prices_and_labels_the_stage(served):
    store, _, _ = served
    cplan = build_cascade(parse_query(q5()), store)
    (stage,) = cplan.stages
    assert stage_kind(stage) == "pair_mass"
    assert 0.0 < stage.est_selectivity < 0.5
    assert set(stage.branches) == {"nMuon"} | {f"Muon_{v}" for v in VARS}


def test_verify_accepts_compiled_and_rejects_malformed():
    prog = compile_query(parse_query(q5()))
    verify_program(prog)
    (grp,) = prog.groups
    bad = [
        Group(grp.kind, grp.term_ids[:4], grp.ops, grp.thrs, cmp_thr=60.0, cmp_thr2=120.0),
        Group(grp.kind, grp.term_ids, (99,), (0.0,), cmp_thr=60.0, cmp_thr2=120.0),
        Group(grp.kind, grp.term_ids, grp.ops, grp.thrs, cmp_thr=120.0, cmp_thr2=60.0),
    ]
    for g in bad:
        with pytest.raises(VerifyError):
            verify_program(type(prog)((g,), prog.term_branches, prog.group_collections,
                                      prog.group_weights, prog.group_collections2))


@pytest.mark.parametrize("node,match", [
    ({"type": "pair_mass", "window": [60, 120]}, "collection"),
    ({"type": "pair_mass", "collection": "Muon", "charge": "neutral",
      "window": [60, 120]}, "charge"),
    ({"type": "pair_mass", "collection": "Muon"}, "window"),
    ({"type": "pair_mass", "collection": "Muon", "window": [60]}, "window"),
    ({"type": "pair_mass", "collection": "Muon", "window": [120, 60]}, "empty"),
])
def test_parse_errors(node, match):
    with pytest.raises(ValueError, match=match):
        parse_query({"selection": {"event": [node]}})


def test_parse_defaults_to_any_charge():
    node = parse_query({"selection": {"event": [
        {"type": "pair_mass", "collection": "Muon", "window": [1, 2]}]}}).event_stage[0]
    assert node == PairMassWindow("Muon", "any", 1.0, 2.0)
    assert node.branches() == {"nMuon"} | {f"Muon_{v}" for v in VARS}


def test_tile_budget_holds_the_pair_temporaries():
    prog = compile_query(parse_query(q5()))
    assert pe.pair_temps(prog) == pe.PAIR_TEMPS
    for K in (8, 16):
        tile = pe.fit_event_tile(sf.EVENT_TILE, prog.n_terms + 2 * prog.n_groups, K,
                                 pe.pair_temps(prog))
        assert tile >= 128 and pe.PAIR_TEMPS * tile * 512 <= pe._TEMP_BUDGET
    # a program without pair groups keeps its tile
    assert pe.fit_event_tile(1024, 7, 8) == pe.fit_event_tile(1024, 7, 8, 0)
