"""The plain reference: what a skim query selects and returns.

A straightforward NumPy evaluation of the query language over the
generated columns, written from the query semantics and importing
nothing of the program.  Each node type has a rule of its own in
``rules/<type>.py``, found by the type's name as metrics are:

* ``cut``: a flat branch compared with a threshold (every
  ``preselection`` node is one);
* ``object``: objects of a collection that pass every cut, counted per
  event against ``min_count`` (every ``object`` node is one);
* ``ht``: the sum of ``var`` over the objects that pass ``object_cuts``;
* ``any``: the OR of boolean branches, an absent branch counting false;
* ``mass``: the invariant mass of the leading pair (the two highest-pt
  objects of one collection, or each collection's leading object; equal
  pt goes to the earlier object), inclusive window;
* ``deltaR``: the distance in (eta, phi) of the leading pair, phi
  wrapped into [-pi, pi).

An ``event`` node's type is its ``type``, ``cut`` if it gives none.  A
rule module gives ``branches(node, tier, present)``, the branches the
node reads (counts branches included), and ``evaluate(sel, tier,
node)``, the node's ``(mask, margin)`` over a :class:`Selection`.  A
configuration whose queries need another node type adds its rule as a
new file; a type with no file is an error that names the file.

Events without a full pair fail ``mass`` and ``deltaR``.  Derived
quantities (HT, mass, deltaR) are computed in the selection's ``dtype``
(``Selection._f``): float64 for the reference, bfloat16 for the
control.  The shared kinematics live here for rules to import: the
leading objects and pair, the textbook four-vector mass (E =
sqrt(pt^2 cosh^2(eta) + m^2)) and the phi wrap.

The output set is the query's ``branches`` patterns matched against
the store's branch names, with ``HLT_*`` standing for the five named
triggers unless ``force_all`` is set (the documented minimal-set rule),
plus every branch the selection reads, plus the counts branch of every
jagged branch kept.
"""

from __future__ import annotations

import fnmatch
import json
import os

import numpy as np

from harness.spec import BENCH_DIR, load_module

#: where ``rules/<type>.py`` are found
RULES_DIR = os.path.join(BENCH_DIR, "rules")

#: output patterns that stand for a fixed minimal set of branches
MINIMAL_SETS = {
    "HLT_*": (
        "HLT_IsoMu24",
        "HLT_Ele32_WPTight_Gsf",
        "HLT_PFMET120_PFMHT120_IDTight",
        "HLT_DoubleEle25_CaloIdL_MW",
        "HLT_Mu17_TrkIsoVVL_Mu8_TrkIsoVVL",
    ),
}

OPS = {
    ">": np.greater,
    ">=": np.greater_equal,
    "<": np.less,
    "<=": np.less_equal,
    "==": np.equal,
    "!=": np.not_equal,
    "abs<": lambda x, v: np.less(np.abs(x), v),
    "abs>": lambda x, v: np.greater(np.abs(x), v),
}

_RULES: dict[str, object] = {}  # rule file path -> its module


def node_type(tier: str, node: dict) -> str:
    """The node type whose rule evaluates ``node`` in ``tier``."""
    if tier == "preselection":
        return "cut"
    if tier == "object":
        return "object"
    return node.get("type", "cut")


def rule(kind: str):
    """The module of ``rules/<kind>.py``; an error naming the file where
    there is none."""
    path = os.path.join(RULES_DIR, f"{kind}.py")
    if path not in _RULES:
        if not kind.isidentifier() or not os.path.isfile(path):
            raise ValueError(f"the reference has no rule for node type {kind!r}: no file {path}")
        _RULES[path] = load_module(path, f"skimbench_rule_{kind}")
    return _RULES[path]


def node_branches(node: dict, tier: str, present) -> set[str]:
    """Branches one selection node reads (counts branches included)."""
    return rule(node_type(tier, node)).branches(node, tier, present)


def selection_nodes(doc: dict) -> list[tuple[str, dict]]:
    """``(tier, node)`` in the order preselection, object, event."""
    sel = doc.get("selection", {})
    return [(t, n) for t in ("preselection", "object", "event") for n in sel.get(t, [])]


class Columns:
    """The generated columns with per-event offsets of each collection."""

    def __init__(self, columns: dict, jagged: dict):
        self.columns = columns
        self.jagged = jagged
        self.n_events = len(columns["event"])
        self._offsets: dict[str, np.ndarray] = {}
        self._event_of: dict[str, np.ndarray] = {}

    def offsets(self, counts_name: str) -> np.ndarray:
        if counts_name not in self._offsets:
            c = self.columns[counts_name].astype(np.int64)
            self._offsets[counts_name] = np.concatenate([[0], np.cumsum(c)])
        return self._offsets[counts_name]

    def event_of(self, coll: str) -> np.ndarray:
        """Event index of every object of a collection."""
        if coll not in self._event_of:
            c = self.columns[f"n{coll}"].astype(np.int64)
            self._event_of[coll] = np.repeat(np.arange(self.n_events), c)
        return self._event_of[coll]

    def window_bytes(self, names, start: int, stop: int) -> int:
        """Decoded bytes of ``names`` over events [start, stop)."""
        total = 0
        for name in names:
            col = self.columns[name]
            if name in self.jagged:
                off = self.offsets(self.jagged[name])
                total += int(off[stop] - off[start]) * col.itemsize
            else:
                total += (stop - start) * col.itemsize
        return total


def leading(cols: Columns, coll: str, k: int):
    """Indices of the ``k`` highest-pt objects of each event and the mask
    of events that have at least ``k`` objects."""
    pt = cols.columns[f"{coll}_pt"].astype(np.float64)
    ev = cols.event_of(coll)
    order = np.lexsort((np.arange(len(pt)), -pt, ev))
    off = cols.offsets(f"n{coll}")
    counts = np.diff(off)
    out = []
    for j in range(k):
        has = counts > j
        idx = np.where(has, order[np.minimum(off[:-1] + j, max(len(order) - 1, 0))], 0)
        out.append((idx, has))
    return out


class Selection:
    """A query's selection over the whole file: per node, the events
    that pass and, for nodes that compute in floating point, each event's
    relative distance of the computed quantity from the cut edge."""

    def __init__(self, cols: Columns, dtype=np.float64):
        self.cols = cols
        self.dtype = dtype
        self._memo: dict[str, tuple[np.ndarray, np.ndarray | None]] = {}

    def node(self, tier: str, node: dict) -> tuple[np.ndarray, np.ndarray | None]:
        key = json.dumps([tier, node], sort_keys=True)
        if key not in self._memo:
            self._memo[key] = self._eval(tier, node)
        return self._memo[key]

    def nodes(self, doc: dict) -> list[tuple[np.ndarray, np.ndarray | None]]:
        return [self.node(t, n) for t, n in selection_nodes(doc)]

    def passed(self, doc: dict) -> np.ndarray:
        mask = np.ones(self.cols.n_events, dtype=bool)
        for m, _ in self.nodes(doc):
            mask &= m
        return mask

    def _f(self, x):
        return np.asarray(x).astype(self.dtype)

    def _eval(self, tier: str, node: dict):
        return rule(node_type(tier, node)).evaluate(self, tier, node)


def collection_branches(collections, variables) -> set[str]:
    """The counts branch and ``<collection>_<var>`` of each collection."""
    out: set[str] = set()
    for c in set(collections):
        out |= {f"n{c}"} | {f"{c}_{v}" for v in variables}
    return out


def leading_pair(sel: Selection, collections, variables):
    """``(p, q, ok)``: the leading pair's ``variables`` in ``sel.dtype``
    (the two highest-pt objects of one collection, or each collection's
    leading object) and the mask of events that have it."""
    a, b = collections
    if a == b:
        (i1, h1), (i2, h2) = leading(sel.cols, a, 2)
        picks, ok = ((a, i1), (a, i2)), h2
    else:
        ((ia, ha),) = leading(sel.cols, a, 1)
        ((ib, hb),) = leading(sel.cols, b, 1)
        picks, ok = ((a, ia), (b, ib)), ha & hb
    c = sel.cols.columns
    out = []
    for coll, idx in picks:
        out.append({
            v: sel._f(c[f"{coll}_{v}"][idx] if len(c[f"{coll}_{v}"]) else np.zeros(len(idx)))
            for v in variables
        })
    return out[0], out[1], ok


def four_vector_mass(f, p: dict, q: dict) -> np.ndarray:
    """Invariant mass of two objects (``pt``, ``eta``, ``phi``, ``mass``),
    every step rounded by ``f``; returned as float64."""

    def four(o):
        pt, eta, phi, m = o["pt"], o["eta"], o["phi"], o["mass"]
        pz = f(pt * np.sinh(eta))
        e = f(np.sqrt(f(f(f(pt * pt) * f(np.cosh(eta) * np.cosh(eta))) + f(m * m))))
        return f(pt * np.cos(phi)), f(pt * np.sin(phi)), pz, e

    px1, py1, pz1, e1 = four(p)
    px2, py2, pz2, e2 = four(q)
    e, px, py, pz = f(e1 + e2), f(px1 + px2), f(py1 + py2), f(pz1 + pz2)
    m2 = f(f(e * e) - f(f(px * px) + f(f(py * py) + f(pz * pz))))
    return f(np.sqrt(np.maximum(m2.astype(np.float64), 0.0))).astype(np.float64)


def wrap_phi(f, dphi: np.ndarray) -> np.ndarray:
    """``dphi`` wrapped into [-pi, pi), rounded by ``f``."""
    two_pi = f(2 * np.pi)
    dphi = np.where(dphi >= f(np.pi), f(dphi - two_pi), dphi)
    return np.where(dphi < f(-np.pi), f(dphi + two_pi), dphi)


def output_branches(doc: dict, cols: Columns) -> list[str]:
    """The branches a query's skim returns (see the module docstring)."""
    names = list(cols.columns)
    out: list[str] = []
    for pat in doc.get("branches", []):
        if not doc.get("force_all") and pat in MINIMAL_SETS:
            out += [n for n in MINIMAL_SETS[pat] if n in cols.columns]
        else:
            out += sorted(fnmatch.filter(names, pat)) or ([pat] if pat in cols.columns else [])
    for tier, node in selection_nodes(doc):
        out += sorted(node_branches(node, tier, cols.columns))
    out += [cols.jagged[n] for n in out if n in cols.jagged]
    return sorted(set(out))


def answer(doc: dict, mask: np.ndarray, cols: Columns) -> dict:
    """The columns a skim with survivor ``mask`` returns."""
    out = {}
    for name in output_branches(doc, cols):
        col = cols.columns[name]
        if name in cols.jagged:
            counts = cols.columns[cols.jagged[name]]
            out[name] = col[np.repeat(mask, counts)]
        else:
            out[name] = col[mask]
    return out
