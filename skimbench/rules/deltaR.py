"""``deltaR``: the distance in (eta, phi) of the leading pair, phi
wrapped into [-pi, pi), compared with ``value``; events without a full
pair fail."""

import numpy as np

from harness.reference import OPS, collection_branches, leading_pair, wrap_phi

VARS = ("pt", "eta", "phi")


def branches(node: dict, tier: str, present) -> set[str]:
    return collection_branches(node["collections"], VARS)


def evaluate(sel, tier: str, node: dict):
    p, q, ok = leading_pair(sel, node["collections"], VARS)
    f = sel._f
    deta = f(p["eta"] - q["eta"])
    dphi = wrap_phi(f, f(p["phi"] - q["phi"]))
    dr = f(np.sqrt(f(f(deta * deta) + f(dphi * dphi)))).astype(np.float64)
    v = node["value"]
    return ok & OPS[node["op"]](dr, v), np.where(ok, np.abs(dr - v) / abs(v), np.inf)
