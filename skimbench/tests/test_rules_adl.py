"""What ``adl_opendata_2012b`` brings as new files: the ``pair_mass``
rule against a brute-force loop, the schema its ``store`` declares, and
the two readers of its cell; ``nanoaod_skim_node``'s generated columns
stay as recorded."""

import json
import math
import os
from types import SimpleNamespace

import ml_dtypes
import numpy as np
import pytest

import test_rules_golden as golden
from harness import drive, spec
from harness.gen import nanoaod_columns
from harness.reference import Columns, Selection, output_branches

CELL = "adl2012b.q5_dimuon"
VARS = ("pt", "eta", "phi", "mass", "charge")
N_EVENTS = 12_000


def _config() -> dict:
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "adl_opendata_2012b")
    return json.load(open(os.path.join(spec.ROOT, entry["file"])))


@pytest.fixture(scope="module")
def cols():
    store = {**_config()["store"], "n_events": N_EVENTS}
    return Columns(*nanoaod_columns(store, 2**31 + 2012))


def _node(charge):
    return {"type": "pair_mass", "collection": "Muon", "charge": charge, "window": [60.0, 120.0]}


def _brute(cols, charge, lo=60.0, hi=120.0):
    c = cols.columns
    off = cols.offsets("nMuon")
    cond = {"opposite": lambda qq: qq < 0, "same": lambda qq: qq > 0, "any": lambda qq: True}
    mask = np.zeros(cols.n_events, dtype=bool)
    margin = np.full(cols.n_events, np.inf)
    for e in range(cols.n_events):
        objs = [[float(c[f"Muon_{v}"][k]) for v in VARS] for k in range(off[e], off[e + 1])]
        for j in range(len(objs)):
            for i in range(j):
                if not cond[charge](objs[i][4] * objs[j][4]):
                    continue
                (p1, e1, f1, m1), (p2, e2, f2, m2) = objs[i][:4], objs[j][:4]
                en = math.hypot(p1 * math.cosh(e1), m1) + math.hypot(p2 * math.cosh(e2), m2)
                px = p1 * math.cos(f1) + p2 * math.cos(f2)
                py = p1 * math.sin(f1) + p2 * math.sin(f2)
                pz = p1 * math.sinh(e1) + p2 * math.sinh(e2)
                m = math.sqrt(max(en * en - px * px - py * py - pz * pz, 0.0))
                mask[e] |= lo < m < hi
                margin[e] = min(margin[e], abs(m - lo) / lo, abs(m - hi) / hi)
    return mask, margin


@pytest.mark.parametrize("charge", ["opposite", "same", "any"])
def test_pair_rule_matches_brute_force(cols, charge):
    mask, margin = Selection(cols).node("event", _node(charge))
    want, want_margin = _brute(cols, charge)
    differ = mask != want
    assert (want_margin[differ] < 1e-9).all()
    assert 0 < want.sum() < cols.n_events
    finite = np.isfinite(want_margin)
    np.testing.assert_array_equal(np.isfinite(margin), finite)
    np.testing.assert_allclose(margin[finite], want_margin[finite], rtol=1e-6, atol=1e-12)


def test_bfloat16_moves_the_pair_mass(cols):
    """The control computes the same rule in bfloat16: decisions near the
    edges move, far more than the check's 1e-4 margin allows."""
    m64, g64 = Selection(cols).node("event", _node("opposite"))
    m16, _ = Selection(cols, dtype=ml_dtypes.bfloat16).node("event", _node("opposite"))
    flips = m64 != m16
    assert flips.any() and (g64[flips] > 1e-4).any()


def test_declared_schema(cols):
    cfg = _config()
    st = cfg["store"]
    c = cols.columns
    assert len(c) == st["n_branches"] == 86
    for coll in st["collections"]:
        names = [f"{coll['name']}_{f['field']}" for f in coll["named"]]
        assert len(names) == coll["fields"] and all(n in c for n in names)
        assert cols.jagged[names[0]] == f"n{coll['name']}"
    assert c["nMuon"].min() >= 1 and (c["nMuon"] == 1).any()
    assert abs(c["nMuon"].mean() - 2.0) < 0.05
    assert set(np.unique(c["Muon_charge"])) == {-1, 1} and c["Muon_charge"].dtype == np.int32
    assert (c["Muon_mass"] == np.float32(0.10566)).all()
    for name in ("run", "luminosityBlock", "event", "HLT_IsoMu24", "PV_npvs", "MET_pt"):
        assert name in c
    doc = json.load(open(os.path.join(spec.BENCH_DIR, "templates", "adl_q5.json")))
    assert output_branches(doc, cols) == sorted(
        ["MET_pt", "event", "nMuon", *(f"Muon_{v}" for v in VARS)]
    )


def test_nanoaod_columns_as_recorded():
    seed = golden.SEEDS[0]
    cols = golden._columns(seed)
    got = golden._hex(*(n.encode() + golden._array(c) for n, c in cols.columns.items()))
    assert got == golden.GOLDEN[seed]["columns"]


# ---------------------------------------------------------------------------
# the cell's readers
# ---------------------------------------------------------------------------


def _ev(cat, ts_s, **args):
    return {"cat": cat, "ph": "X", "ts": ts_s * 1e6, "dur": 1e4, "pid": 7, "args": args}


def test_pair_fill_reader_sums_spans_in_the_window():
    spans = [
        _ev("device_launch", 9.0, op="fused_skim", pair_slots=1_000, pairs_live=999),
        _ev("device_launch", 11.0, op="fused_skim", pair_slots=28 * 4096, pairs_live=6_000),
        _ev("device_launch", 12.0, op="basket_decode", h2d_bytes=10),
        _ev("device_launch", 13.0, op="fused_skim", pair_slots=120 * 4096, pairs_live=6_500),
        _ev("device_launch", 20.0, op="fused_skim", pair_slots=1_000, pairs_live=999),
    ]
    run = SimpleNamespace(t0=10.0, t_cut=20.0, spans=spans)
    reader = spec._reader("cascade.pair_fill")
    assert reader.read(run) == pytest.approx(12_500 / (148 * 4096), rel=1e-12)
    run.spans = spans[2:3]
    assert reader.read(run) is None


def test_pair_roofline_reader_takes_the_larger_bound(cols):
    doc = json.load(open(os.path.join(spec.BENCH_DIR, "templates", "adl_q5.json")))
    spans = [
        {"pid": 3, "cat": "window", "ts": 1e6, "args": {"sid": 2, "index": 1}},
        {"pid": 3, "cat": "cascade_stage", "ts": 1.1e6, "args": {"sid": 4, "parent": 2, "stage": 0}},
    ]
    reader = spec._reader("kernels.pair_mass_roofline")

    def run(kernel_s, flop_per_s):
        return SimpleNamespace(
            t0=0.0, t_cut=10.0, spans=spans, columns=cols,
            records=[SimpleNamespace(job=SimpleNamespace(job_id=3), doc=doc)],
            cell=SimpleNamespace(config={"store": {"basket_events": 4096}}),
            kernels={"predicate": "skim_fused"},
            device_trace=SimpleNamespace(kernel_s=lambda pattern: kernel_s),
            peaks={"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": flop_per_s},
        )

    n = cols.columns["nMuon"][4096:8192].astype(np.int64)
    ops = reader.OPS_PER_PAIR * int((n * (n - 1) // 2).sum()) + reader.OPS_PER_SLOT * int(n.sum())
    names = ["nMuon", *(f"Muon_{v}" for v in VARS)]
    nbytes = cols.window_bytes(names, 4096, 8192)
    assert reader.pair_ops(run(1e-3, 197e12)) == ops
    assert reader.read(run(1e-3, 197e12)) == pytest.approx(100 * nbytes / 819e9 / 1e-3)
    assert reader.read(run(1e-3, 1e9)) == pytest.approx(100 * ops / 1e9 / 1e-3)
    assert reader.read(run(0.0, 197e12)) is None


def test_pair_fill_from_the_served_spans_equals_the_column_count(monkeypatch, tmp_path):
    """The harness's traced window on the CPU: the counters the program's
    launches carry add up to the count from the generated columns, window
    by window (each window one launch of 4,096 rows at the window's K)."""
    from repro.data import store as store_mod

    orig = store_mod.EventStore.from_arrays.__func__

    def device_decode(cls, *a, **kw):
        return orig(cls, *a, **{**kw, "decode_backend": "device"})

    monkeypatch.setattr(store_mod.EventStore, "from_arrays", classmethod(device_decode))
    cell = spec.load_cell(CELL)
    cell.config["store"]["n_events"] = 8_192
    cell.config["engine"] = {"fused_backend": "pallas"}
    counter = drive.CompileCounter()
    run = drive.setup(cell, 2**31 + 7, 1.0, True)
    drive.window(run, 1.0, counter, str(tmp_path))
    fill = spec._reader("cascade.pair_fill").read(run)
    lo, hi = run.t0 * 1e6, run.t_cut * 1e6
    windows = [e["args"]["index"] for e in run.spans
               if e["cat"] == "window" and lo <= e["ts"] < hi and e["pid"] < 10_000]
    assert windows
    live = slots = 0
    n = run.columns.columns["nMuon"].astype(np.int64)
    for w in windows:
        c = n[w * 4096:(w + 1) * 4096]
        K = 1 << int(c.max() - 1).bit_length()
        live += int((c * (c - 1) // 2).sum())
        slots += 4096 * K * (K - 1) // 2
    assert fill == live / slots
