"""``mass``: the invariant mass of the leading pair, in the inclusive
``window``; events without a full pair fail."""

import numpy as np

from harness.reference import collection_branches, four_vector_mass, leading_pair

VARS = ("pt", "eta", "phi", "mass")


def branches(node: dict, tier: str, present) -> set[str]:
    return collection_branches(node["collections"], VARS)


def evaluate(sel, tier: str, node: dict):
    p, q, ok = leading_pair(sel, node["collections"], VARS)
    m = four_vector_mass(sel._f, p, q)
    lo, hi = node["window"]
    inside = ok & (m >= lo) & (m <= hi)
    margin = np.minimum(np.abs(m - lo) / abs(lo), np.abs(m - hi) / abs(hi))
    return inside, np.where(ok, margin, np.inf)
