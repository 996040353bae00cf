"""``pair_mass``: some pair of two distinct objects of one collection,
taken from all pairs, whose charge product meets ``charge``
(``"opposite"``: q_i·q_j < 0, ``"same"``: > 0, ``"any"``, the default)
and whose invariant mass lies in the open ``window`` (lo < m < hi): the
ADL benchmark's Query 5 shape.

The pairs are enumerated by a plain loop over slot pairs (i, j), i < j,
up to the largest multiplicity in the file.  An event's margin is the
smallest relative distance from a window edge over the pairs that meet
the charge condition; an event with no such pair has none (infinite)."""

import numpy as np

from harness.reference import collection_branches, four_vector_mass

VARS = ("pt", "eta", "phi", "mass", "charge")
CHARGES = {
    "opposite": lambda qq: qq < 0,
    "same": lambda qq: qq > 0,
    "any": lambda qq: np.ones(len(qq), dtype=bool),
}


def branches(node: dict, tier: str, present) -> set[str]:
    return collection_branches([node["collection"]], VARS)


def pairs(cols, coll: str):
    """``(event, first, second)`` value indices of every pair i < j."""
    off = cols.offsets(f"n{coll}")
    counts = np.diff(off)
    events, first, second = [], [], []
    for j in range(1, int(counts.max(initial=0))):
        has = np.flatnonzero(counts > j)
        for i in range(j):
            events.append(has)
            first.append(off[has] + i)
            second.append(off[has] + j)
    if not events:
        return (np.zeros(0, np.int64),) * 3
    return np.concatenate(events), np.concatenate(first), np.concatenate(second)


def evaluate(sel, tier: str, node: dict):
    coll = node["collection"]
    c = sel.cols.columns
    ev, a, b = pairs(sel.cols, coll)
    p = {v: sel._f(c[f"{coll}_{v}"][a]) for v in VARS[:4]}
    q = {v: sel._f(c[f"{coll}_{v}"][b]) for v in VARS[:4]}
    m = four_vector_mass(sel._f, p, q)
    qq = c[f"{coll}_charge"][a].astype(np.int64) * c[f"{coll}_charge"][b]
    ok = CHARGES[node.get("charge", "any")](qq)
    lo, hi = node["window"]
    n = sel.cols.n_events
    inside = np.bincount(ev[ok & (m > lo) & (m < hi)], minlength=n) > 0
    margin = np.full(n, np.inf)
    dist = np.minimum(np.abs(m - lo) / abs(lo), np.abs(m - hi) / abs(hi))
    np.minimum.at(margin, ev[ok], dist[ok])
    return inside, margin
