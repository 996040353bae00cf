"""Derived-expression query tier (DESIGN.md §10).

Pins the tentpole contracts:

  * the expression language parses/evaluates correctly (precedence,
    functions, sum() reductions, error cases),
  * mass/ΔR leading-pair kinematics match hand-computed physics,
  * derived queries are bit-identical across the staged evaluator, the
    compiled-program host interpreter, the xla device backend, fused and
    pruned engine modes, shared-scan, and the cluster,
  * zone-map interval analysis over expression trees prunes provably
    empty windows and never drops a survivor (deterministic edges here;
    the random property tests are hypothesis-guarded).
"""

import numpy as np
import pytest

from repro.core import expr as xpr
from repro.core.engine import SkimEngine, run_skim
from repro.core.neardata import fused_window_skim, program_eval_np
from repro.core.planner import plan_skim
from repro.core.query import eval_node, eval_stage, parse_query
from repro.core.zonemap import ACCEPT_ALL, PRUNE, SCAN, classify_windows
from repro.data.store import EventStore
from repro.data.synth import make_nanoaod_like
from repro.serve.engine import SharedScanEngine

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal installs
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# expression language
# ---------------------------------------------------------------------------


def _eval(text, data):
    return xpr.eval_expr_np(xpr.to_rpn(xpr.parse_expr(text)), data)


def test_expr_precedence_and_functions():
    data = {"a": np.array([2.0, -3.0]), "b": np.array([4.0, 5.0])}
    np.testing.assert_array_equal(_eval("a + 2*b", data), [10.0, 7.0])
    np.testing.assert_array_equal(_eval("(a + 2) * b", data), [16.0, -5.0])
    np.testing.assert_array_equal(_eval("-a", data), [-2.0, 3.0])
    np.testing.assert_array_equal(_eval("abs(a - b)", data), [2.0, 8.0])
    np.testing.assert_array_equal(_eval("min(a, b)", data), [2.0, -3.0])
    np.testing.assert_array_equal(_eval("max(a, b) - 1", data), [3.0, 4.0])
    np.testing.assert_array_equal(_eval("a / b", data), [0.5, -0.6])
    np.testing.assert_array_equal(_eval("a - b - 1", data), [-3.0, -9.0])


def test_expr_sum_reduction_is_float64_segment_sum():
    data = {
        "nObj": np.array([2, 0, 1], dtype=np.int32),
        "Obj_pt": np.array([1.5, 2.5, 7.0], dtype=np.float32),
        "met": np.array([10.0, 20.0, 30.0], dtype=np.float32),
    }
    np.testing.assert_array_equal(_eval("sum(Obj_pt)", data), [4.0, 0.0, 7.0])
    np.testing.assert_array_equal(
        _eval("met + 0.5*sum(Obj_pt)", data), [12.0, 20.0, 33.5]
    )


@pytest.mark.parametrize("bad", [
    "1 + 1",          # no branches: constant predicate
    "a +",            # dangling operator
    "foo(a)",         # unknown function
    "min(a)",         # wrong arity
    "sum(1)",         # sum needs a branch identifier
    "a $ b",          # bad character
    "(a",             # unbalanced paren
    "a b",            # trailing input
])
def test_expr_rejects_malformed(bad):
    with pytest.raises(ValueError):
        xpr.compile_expr(bad)


def test_expr_branch_discovery_includes_sum_counts():
    rpn = xpr.compile_expr("MET_pt + sum(Jet_pt)/2")
    assert xpr.rpn_branches(rpn) == {"MET_pt", "Jet_pt", "nJet"}


def test_expr_validation_against_store():
    store = make_nanoaod_like(200, n_hlt=4)
    # bare jagged branch must be rejected (use sum() or an object node)
    q = parse_query({"branches": ["MET_*"], "selection": {"event": [
        {"type": "expr", "expr": "Jet_pt + 1", "op": ">", "value": 0.0}]}})
    with pytest.raises(ValueError, match="jagged"):
        plan_skim(q, store)
    # sum() of a flat branch is equally malformed
    q2 = parse_query({"branches": ["MET_*"], "selection": {"event": [
        {"type": "expr", "expr": "sum(MET_pt)", "op": ">", "value": 0.0}]}})
    with pytest.raises(ValueError, match="jagged"):
        plan_skim(q2, store)


# ---------------------------------------------------------------------------
# leading-pair kinematics
# ---------------------------------------------------------------------------


def _pair_data(**over):
    """Three events: [2e back-to-back], [1e], [3e with a soft leader tie]."""
    base = {
        "nElectron": np.array([2, 1, 3], dtype=np.int32),
        "Electron_pt": np.array([40.0, 40.0, 25.0, 30.0, 10.0, 30.0],
                                dtype=np.float32),
        "Electron_eta": np.array([0.0, 0.0, 1.0, 0.5, 0.0, -0.5],
                                 dtype=np.float32),
        "Electron_phi": np.array([0.0, np.pi, 2.0, 1.0, 0.0, -1.0],
                                 dtype=np.float32),
        "Electron_mass": np.zeros(6, dtype=np.float32),
    }
    base.update(over)
    return base


def test_mass_back_to_back_pair():
    # massless, equal pt, opposite phi, eta 0: E = 40 + 40, p cancels -> 80
    m, ok = xpr.leading_pair_mass(_pair_data(), "Electron", "Electron")
    assert ok.tolist() == [True, False, True]
    assert m[0] == pytest.approx(80.0, rel=1e-12)


def test_mass_kernel_float32_tracks_float64_host():
    """The device's float32 pair mass stays within a few ulp of the
    float64 host helper near typical windows: no E² − p² cancellation
    for boosted pairs (the textbook form is off by ~4e-5 here)."""
    import jax.numpy as jnp

    from repro.kernels import ref

    rng = np.random.default_rng(5)
    n = 50_000

    def obj():
        return (
            (rng.exponential(25.0, n) + 3.0).astype(np.float32),
            rng.uniform(-2.5, 2.5, n).astype(np.float32),
            rng.uniform(-np.pi, np.pi, n).astype(np.float32),
            np.abs(rng.normal(5.0, 3.0, n)).astype(np.float32),
        )

    a, b = obj(), obj()
    data = {"nElectron": np.full(n, 2, np.int32)}
    for i, var in enumerate(("pt", "eta", "phi", "mass")):
        data[f"Electron_{var}"] = np.stack([a[i], b[i]], 1).ravel()
    m64, ok = xpr.leading_pair_mass(data, "Electron", "Electron")
    m32 = np.asarray(
        ref._pair_mass([jnp.asarray(x) for x in a], [jnp.asarray(x) for x in b]),
        np.float64,
    )
    near = ok & (m64 > 40.0) & (m64 < 140.0)
    assert near.sum() > 10_000
    assert (np.abs(m32 - m64) / m64)[near].max() < 2e-6


def test_mass_window_node_insufficient_objects_fail():
    node = parse_query({"selection": {"event": [
        {"type": "mass", "collections": ["Electron", "Electron"],
         "window": [0.0, 1e9]}]}}).event_stage[0]
    mask = eval_node(node, _pair_data(), 3)
    # the wide-open window passes every event that HAS a pair; event 1
    # (single electron) fails regardless
    assert mask.tolist() == [True, False, True]


def test_mass_leading_pair_ties_use_storage_order():
    """Event 2 has pt (30, 10, 30): the leading pair is the tied 30s in
    storage order — matching the device argmax first-occurrence tiebreak."""
    data = _pair_data()
    (i1, i2), _ = xpr._leading_indices(
        data["Electron_pt"][3:], np.array([3]), 2
    )
    assert (int(i1[0]), int(i2[0])) == (0, 2)


def test_delta_r_wraps_phi():
    data = {
        "nElectron": np.array([1], dtype=np.int32),
        "Electron_pt": np.array([50.0], dtype=np.float32),
        "Electron_eta": np.array([0.3], dtype=np.float32),
        "Electron_phi": np.array([3.0], dtype=np.float32),
        "nJet": np.array([1], dtype=np.int32),
        "Jet_pt": np.array([60.0], dtype=np.float32),
        "Jet_eta": np.array([0.3], dtype=np.float32),
        "Jet_phi": np.array([-3.0], dtype=np.float32),
    }
    dr, ok = xpr.leading_delta_r(data, "Electron", "Jet")
    assert ok[0]
    # dphi = 6.0 wrapped to 2*pi - 6.0
    want = abs(2 * np.pi - 6.0)
    assert dr[0] == pytest.approx(want, rel=1e-6)


def test_delta_r_mixed_pair_picks_each_leading():
    data = {
        "nElectron": np.array([2], dtype=np.int32),
        "Electron_pt": np.array([10.0, 90.0], dtype=np.float32),
        "Electron_eta": np.array([2.0, 0.0], dtype=np.float32),
        "Electron_phi": np.array([1.0, 0.0], dtype=np.float32),
        "nJet": np.array([2], dtype=np.int32),
        "Jet_pt": np.array([80.0, 20.0], dtype=np.float32),
        "Jet_eta": np.array([1.0, -2.0], dtype=np.float32),
        "Jet_phi": np.array([0.0, 3.0], dtype=np.float32),
    }
    dr, ok = xpr.leading_delta_r(data, "Electron", "Jet")
    # leading e is index 1 (eta 0, phi 0), leading jet index 0 (eta 1, phi 0)
    assert ok[0] and dr[0] == pytest.approx(1.0, rel=1e-12)


# ---------------------------------------------------------------------------
# end-to-end bit-identity across every executor
# ---------------------------------------------------------------------------

ZQUERY = {
    "branches": ["Electron_*", "Jet_pt", "MET_*", "luminosityBlock"],
    "selection": {
        "event": [
            {"type": "mass", "collections": ["Electron", "Electron"],
             "window": [5.0, 120.0]},
            {"type": "deltaR", "collections": ["Electron", "Jet"],
             "op": ">", "value": 0.4},
            {"type": "expr", "expr": "MET_pt + 0.5*sum(Jet_pt)",
             "op": ">", "value": 60.0},
        ],
    },
}


@pytest.fixture(scope="module")
def store():
    return make_nanoaod_like(8_000, n_hlt=8, n_filler=4, basket_events=1024)


@pytest.fixture(scope="module")
def reference(store):
    return run_skim(store, ZQUERY, mode="near_data", fused=False,
                    pipeline=False, prune=False)


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


def test_reference_selects_something(reference, store):
    assert 0 < reference.n_passed < store.n_events


@pytest.mark.parametrize("kw", [
    dict(fused=True, pipeline=False, prune=False),
    dict(fused=True, pipeline=True, prune=False),
    dict(fused=True, pipeline="threads", prune=False),
    dict(fused=False, pipeline=False, prune=True),
    dict(fused=True, pipeline=True, prune=True),
])
def test_derived_query_modes_bit_identical(store, reference, kw):
    res = run_skim(store, ZQUERY, mode="near_data", **kw)
    _assert_same_output(res, reference)


def test_derived_query_shared_scan_matches_solo(store):
    tenants = [ZQUERY,
               {"branches": ["MET_*"], "selection": {"event": [
                   {"type": "expr", "expr": "MET_pt*2", "op": ">",
                    "value": 80.0}]}}]
    batch = SharedScanEngine(store).run_batch(tenants)
    eng = SkimEngine(store)
    for q, res in zip(tenants, batch.results):
        _assert_same_output(res, eng.run(q, "near_data"))


def test_derived_query_cluster_matches_single_node(store, reference):
    from repro.cluster.coordinator import build_cluster

    res = build_cluster(store, 4).run(ZQUERY)
    assert res.n_passed == reference.n_passed
    _assert_same_output(res, reference)


@pytest.mark.parametrize("backend", ["host", "xla", "pallas"])
def test_derived_fused_window_backends_agree(store, backend):
    q = parse_query(ZQUERY)
    plan = plan_skim(q, store)
    data = {}
    for b in plan.filter_branches:
        br = store.branches[b]
        data[b] = store.read_jagged(b)[0] if br.jagged else store.read_flat(b)
    n = store.n_events
    want = np.ones(n, dtype=bool)
    for _, stage in q.stages():
        want &= eval_stage(stage, data, n)
    mask, _ = fused_window_skim(
        data, plan.compiled_program(), store, backend=backend
    )
    np.testing.assert_array_equal(mask, want)


def test_program_interpreter_matches_staged_for_derived_nodes(store):
    queries = [
        {"branches": ["MET_*"], "selection": {"event": [
            {"type": "expr", "expr": "abs(MET_pt - 30)", "op": "<",
             "value": 10.0}]}},
        {"branches": ["MET_*"], "selection": {"event": [
            {"type": "expr", "expr": "min(MET_pt, sum(Jet_pt))", "op": ">",
             "value": 25.0}]}},
        {"branches": ["Electron_*"], "selection": {"event": [
            {"type": "mass", "collections": ["Electron", "Electron"],
             "window": [0.0, 60.0]}]}},
        {"branches": ["Electron_*"], "selection": {"event": [
            {"type": "deltaR", "collections": ["Electron", "Muon"],
             "op": "<", "value": 2.0}]}},
        {"branches": ["Electron_*"], "selection": {"event": [
            {"type": "deltaR", "collections": ["Jet", "Jet"],
             "op": ">", "value": 1.0}]}},
    ]
    n = store.n_events
    for doc in queries:
        q = parse_query(doc)
        plan = plan_skim(q, store)
        data = {}
        for b in plan.filter_branches:
            br = store.branches[b]
            data[b] = (
                store.read_jagged(b)[0] if br.jagged else store.read_flat(b)
            )
        want = np.ones(n, dtype=bool)
        for _, stage in q.stages():
            want &= eval_stage(stage, data, n)
        got = program_eval_np(data, plan.compiled_program(), n)
        np.testing.assert_array_equal(got, want, err_msg=str(doc))


# ---------------------------------------------------------------------------
# zone-map interval analysis over expressions
# ---------------------------------------------------------------------------

BASKET = 32


def _spans(store, window_events=BASKET):
    return [
        (s, min(s + window_events, store.n_events))
        for s in range(0, store.n_events, window_events)
    ]


def _expr_query(expr, op, value):
    return parse_query({"branches": ["met"], "selection": {"event": [
        {"type": "expr", "expr": expr, "op": op, "value": value}]}})


def _check_window_invariants(query, store, columns, jagged=None):
    """PRUNE windows hold no survivor, ACCEPT_ALL windows no failure."""
    jagged = jagged or {}
    for (a, b), kind in zip(
        spans := _spans(store), classify_windows(query, store, spans)
    ):
        data = {}
        for name, arr in columns.items():
            if name in jagged:
                counts = columns[jagged[name]]
                off = np.concatenate([[0], np.cumsum(counts)])
                data[name] = arr[off[a]:off[b]]
            else:
                data[name] = arr[a:b]
        mask = np.ones(b - a, dtype=bool)
        for _, stage in query.stages():
            mask &= eval_stage(stage, data, b - a)
        if kind == PRUNE:
            assert not mask.any(), (a, b)
        elif kind == ACCEPT_ALL:
            assert mask.all(), (a, b)


def test_expr_interval_prunes_monotone_ramp():
    n = 4 * BASKET
    columns = {
        "met": np.full(n, 10.0, dtype=np.float32),
        "ramp": np.arange(n, dtype=np.float32),
    }
    store = EventStore.from_arrays(columns, basket_events=BASKET)
    q = _expr_query("2*ramp + 0.1*met", "<", 2.0 * BASKET)
    kinds = classify_windows(q, store, _spans(store))
    assert kinds[0] == ACCEPT_ALL  # 2*31 + 1 < 64 for the whole window
    assert kinds[2] == PRUNE and kinds[3] == PRUNE
    _check_window_invariants(q, store, columns)


def test_expr_interval_division_by_straddling_interval_scans():
    n = 2 * BASKET
    columns = {
        # every window straddles zero: the divisor interval may vanish
        "met": np.tile(np.array([-3.0, 4.0], np.float32), n // 2),
        "x": np.full(n, 1.0, dtype=np.float32),
    }
    store = EventStore.from_arrays(columns, basket_events=BASKET)
    q = _expr_query("x / met", ">", 1000.0)
    assert classify_windows(q, store, _spans(store)) == [SCAN, SCAN]
    # a strictly positive divisor is decidable again: |met/x| <= 4
    q2 = _expr_query("met / x", ">", 1000.0)
    assert classify_windows(q2, store, _spans(store)) == [PRUNE, PRUNE]


def test_expr_interval_sum_zero_objects_is_exact():
    n = 2 * BASKET
    counts = np.zeros(n, dtype=np.int32)
    counts[:BASKET] = 2  # objects only in the first window
    total = int(counts.sum())
    columns = {
        "met": np.full(n, 50.0, dtype=np.float32),
        "nObj": counts,
        "Obj_pt": np.full(total, 30.0, dtype=np.float32),
    }
    store = EventStore.from_arrays(
        columns, jagged={"Obj_pt": "nObj"}, basket_events=BASKET
    )
    q = _expr_query("sum(Obj_pt)", ">", 5.0)
    kinds = classify_windows(q, store, _spans(store))
    # second window: no objects anywhere, the sum is exactly 0.0 -> PRUNE
    assert kinds[1] == PRUNE
    _check_window_invariants(q, store, columns, {"Obj_pt": "nObj"})


def test_mass_and_deltar_degrade_to_scan():
    store = make_nanoaod_like(4 * BASKET, n_hlt=4, basket_events=BASKET)
    q = parse_query({"branches": ["Electron_*"], "selection": {"event": [
        {"type": "mass", "collections": ["Electron", "Electron"],
         "window": [80.0, 100.0]}]}})
    assert set(classify_windows(q, store, _spans(store))) == {SCAN}
    q2 = parse_query({"branches": ["Electron_*"], "selection": {"event": [
        {"type": "deltaR", "collections": ["Electron", "Jet"],
         "op": ">", "value": 0.4}]}})
    assert set(classify_windows(q2, store, _spans(store))) == {SCAN}


# ---------------------------------------------------------------------------
# property tests: random expressions never prune a survivor
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    _leaf = st.sampled_from(["met", "cnt", "sum(Obj_pt)", "3", "0.25", "-2"])
    _binop = st.sampled_from(["+", "-", "*"])

    @st.composite
    def _random_expr(draw) -> str:
        depth = draw(st.integers(1, 3))

        def build(d: int) -> str:
            if d <= 0 or draw(st.booleans()):
                return draw(_leaf)
            shape = draw(st.integers(0, 3))
            if shape == 0:
                return f"abs({build(d - 1)})"
            if shape == 1:
                fn = draw(st.sampled_from(["min", "max"]))
                return f"{fn}({build(d - 1)}, {build(d - 1)})"
            return f"({build(d - 1)} {draw(_binop)} {build(d - 1)})"

        text = build(depth)
        # guarantee at least one branch reference
        if not (set("abcdefghijklmnopqrstuvwxyz") - set("sum")) & set(text):
            text = f"met + {text}"
        return text

    @st.composite
    def _random_case(draw):
        seed = draw(st.integers(0, 2**16))
        n_events = draw(st.integers(33, 129))
        rng = np.random.default_rng(seed)
        counts = rng.poisson(draw(st.floats(0.0, 2.5)), n_events).astype(
            np.int32
        )
        columns = {
            "met": rng.normal(30.0, 25.0, n_events).astype(np.float32),
            "cnt": rng.integers(-5, 40, n_events).astype(np.int32),
            "nObj": counts,
            "Obj_pt": (
                rng.exponential(25.0, int(counts.sum())) - 10.0
            ).astype(np.float32),
        }
        doc = {
            "branches": ["met", "Obj_*", "cnt"],
            "selection": {"event": [{
                "type": "expr",
                "expr": draw(_random_expr()),
                "op": draw(st.sampled_from(
                    [">", ">=", "<", "<=", "==", "!=", "abs<", "abs>"]
                )),
                "value": draw(st.one_of(
                    st.floats(-150.0, 150.0, allow_nan=False,
                              allow_infinity=False),
                    st.sampled_from([0.0, 1.0, 30.0, -30.0]),
                )),
            }]},
        }
        return columns, doc

    @given(_random_case())
    @settings(max_examples=150, deadline=None)
    def test_expr_interval_never_prunes_a_survivor(case):
        columns, doc = case
        jagged = {"Obj_pt": "nObj"}
        store = EventStore.from_arrays(
            columns, jagged=jagged, basket_events=BASKET
        )
        try:
            query = parse_query(doc)
        except ValueError:
            return  # constant-only random expression: rejected by parse
        _check_window_invariants(query, store, columns, jagged)

    @given(_random_case())
    @settings(max_examples=60, deadline=None)
    def test_expr_engine_prune_bit_identical(case):
        columns, doc = case
        jagged = {"Obj_pt": "nObj"}
        store = EventStore.from_arrays(
            columns, jagged=jagged, basket_events=BASKET
        )
        try:
            query = parse_query(doc)
        except ValueError:
            return
        ref = run_skim(store, query, mode="near_data", fused=False,
                       pipeline=False, prune=False)
        res = run_skim(store, query, mode="near_data", fused=True,
                       pipeline=False, prune=True)
        assert res.n_passed == ref.n_passed
