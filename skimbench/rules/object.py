"""``object``: the objects of a collection that pass every cut, counted
per event against ``min_count``.  Every ``object`` node is one."""

import numpy as np

from harness.reference import OPS


def branches(node: dict, tier: str, present) -> set[str]:
    c = node["collection"]
    return {f"n{c}"} | {f"{c}_{cut['var']}" for cut in node.get("cuts", [])}


def evaluate(sel, tier: str, node: dict):
    c = sel.cols.columns
    coll = node["collection"]
    ev = sel.cols.event_of(coll)
    ok = np.ones(len(ev), dtype=bool)
    for cut in node.get("cuts", []):
        ok &= OPS[cut["op"]](c[f"{coll}_{cut['var']}"], cut["value"])
    n = np.bincount(ev[ok], minlength=sel.cols.n_events)
    return n >= node.get("min_count", 1), None
