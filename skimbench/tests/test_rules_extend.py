"""A configuration brings its own selection rules and event schema as new
files, with no harness module edited: a toy node type in a rules
directory of its own, and a toy store that declares its named fields and
a multiplicity floor, through the reference, ``work.predicate_bytes`` and
``output_branches``.  A node type with no rule file fails loudly."""

import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from harness import reference, work
from harness.gen import nanoaod_columns
from harness.reference import Columns, Selection, node_branches, output_branches

SUM_RULE = '''"""``sum``: the sum of ``var`` over a collection's objects, in the
selection's dtype, compared with ``value``."""

import numpy as np

from harness.reference import OPS


def branches(node, tier, present):
    c = node["collection"]
    return {f"n{c}", f"{c}_{node['var']}"}


def evaluate(sel, tier, node):
    c = node["collection"]
    vals = sel._f(sel.cols.columns[f"{c}_{node['var']}"]).astype(np.float64)
    total = np.bincount(sel.cols.event_of(c), weights=vals, minlength=sel.cols.n_events)
    v = node["value"]
    return OPS[node["op"]](total, v), np.abs(total - v) / abs(v)
'''

MUON = [
    {"field": "pt", "dist": "exponential", "scale": 20.0, "offset": 3.0, "dtype": "float32"},
    {"field": "eta", "dist": "uniform", "low": -2.4, "high": 2.4, "dtype": "float32"},
    {"field": "phi", "dist": "uniform", "low": -3.141592653589793, "high": 3.141592653589793,
     "dtype": "float32"},
    {"field": "mass", "dist": "abs_normal", "loc": 0.1057, "scale": 0.0, "dtype": "float32"},
    {"field": "charge", "dist": "choice", "values": [-1, 1], "dtype": "int32"},
    {"field": "pfRelIso03_all", "dist": "beta", "a": 0.5, "b": 5.0, "dtype": "float32"},
    {"field": "tightId", "dist": "bernoulli", "p": 0.8, "dtype": "bool"},
    {"field": "nStations", "dist": "poisson", "lam": 3.0, "dtype": "int32"},
]
STORE = {
    "n_events": 6_000,
    "collections": [
        {"name": "Muon", "mean": 1.6, "min": 1, "fields": 10, "named": MUON},
        {"name": "Jet", "mean": 4.0, "fields": 6},
    ],
    "flat": [
        {"prefix": "ids", "fields": 3, "kind": "ids"},
        {"prefix": "MET", "fields": 3, "named": [
            {"field": "pt", "dist": "exponential", "scale": 30.0, "dtype": "float32"},
        ]},
        {"prefix": "HLT", "fields": 4, "kind": "trigger", "named": [
            {"field": "IsoMu24", "dist": "bernoulli", "p": 1.0, "dtype": "bool"},
        ]},
    ],
}
SUM_NODE = {"type": "sum", "collection": "Muon", "var": "pfRelIso03_all", "op": "<", "value": 0.3}
DOC = {
    "branches": ["MET_pt", "run", "event"],
    "selection": {
        "preselection": [{"branch": "nMuon", "op": ">=", "value": 1}],
        "object": [{"collection": "Muon", "cuts": [{"var": "tightId", "op": "==", "value": True}]}],
        "event": [SUM_NODE, {"type": "mass", "collections": ["Muon", "Muon"], "window": [60, 120]}],
    },
}


@pytest.fixture
def rules_dir(tmp_path, monkeypatch):
    """The six rules of the benchmark and the toy ``sum``, in a directory
    the reference is pointed at."""
    d = tmp_path / "rules"
    shutil.copytree(reference.RULES_DIR, d, ignore=shutil.ignore_patterns("__pycache__"))
    (d / "sum.py").write_text(SUM_RULE)
    monkeypatch.setattr(reference, "RULES_DIR", str(d))
    return d


@pytest.fixture(scope="module")
def cols():
    return Columns(*nanoaod_columns(STORE, 2**31 + 77))


def test_declared_schema(cols):
    c = cols.columns
    assert list(c)[:11] == ["nMuon", *(f"Muon_{f['field']}" for f in MUON), "Muon_v08", "Muon_v09"]
    for f in MUON:
        assert c[f"Muon_{f['field']}"].dtype == np.dtype(f["dtype"])
    assert set(np.unique(c["Muon_charge"])) == {-1, 1}
    assert c["HLT_IsoMu24"].all() and c["HLT_IsoMu24"].dtype == bool
    assert list(c)[-4:] == ["HLT_IsoMu24", "HLT_v01", "HLT_v02", "HLT_v03"]
    assert "Jet_btagDeepB" in c and "Jet_v05" in c  # undeclared: NAMED's fields
    assert len(c) == 1 + 10 + 1 + 6 + 3 + 3 + 4


def test_min_floor_holds(cols):
    n = cols.columns["nMuon"]
    assert n.min() >= 1 and (n == 1).any()
    assert abs(n.mean() - 1.6) < 0.05
    assert (cols.columns["nJet"] == 0).any()  # no floor where none is declared


def test_toy_node_through_the_reference(rules_dir, cols):
    iso = cols.columns["Muon_pfRelIso03_all"].astype(np.float64)
    total = np.bincount(cols.event_of("Muon"), weights=iso, minlength=cols.n_events)
    mask, margin = Selection(cols).node("event", SUM_NODE)
    np.testing.assert_array_equal(mask, total < 0.3)
    assert 0 < mask.sum() < cols.n_events and margin is not None
    assert node_branches(SUM_NODE, "event", cols.columns) == {"nMuon", "Muon_pfRelIso03_all"}
    passed = Selection(cols).passed(DOC)
    assert 0 < passed.sum() < mask.sum()
    out = output_branches(DOC, cols)
    assert {"Muon_pfRelIso03_all", "Muon_tightId", "nMuon", "MET_pt"} <= set(out)


def test_toy_node_through_work(rules_dir, cols):
    """``predicate_bytes`` counts the toy stage's branches over its window."""
    stage = {"sid": 12, "parent": 11, "stage": 2}  # preselection, object, then the toy node
    spans = [
        {"pid": 7, "cat": "window", "ts": 1e6, "args": {"sid": 11, "index": 2}},
        {"pid": 7, "cat": "cascade_stage", "ts": 1.5e6, "args": stage},
    ]
    run = SimpleNamespace(
        t0=0.0, t_cut=10.0, spans=spans, columns=cols,
        records=[SimpleNamespace(job=SimpleNamespace(job_id=7), doc=DOC)],
        cell=SimpleNamespace(config={"store": {"basket_events": 1_000}}),
    )
    want = cols.window_bytes(["Muon_pfRelIso03_all", "nMuon"], 2_000, 3_000)
    assert work.predicate_bytes(run) == want > 0


@pytest.mark.parametrize("use", ["evaluate", "branches"])
def test_unknown_type_names_its_file(use, cols):
    node = {"type": "sum", "collection": "Muon", "var": "pt", "op": ">", "value": 1.0}
    with pytest.raises(ValueError, match=r"skimbench/rules/sum\.py"):
        if use == "evaluate":
            Selection(cols).node("event", node)
        else:
            node_branches(node, "event", cols.columns)
