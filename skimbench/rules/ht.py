"""``ht``: the sum of ``var`` (default ``pt``) over the objects of a
collection that pass ``object_cuts``, accumulated object by object in
storage order in the selection's ``dtype``, compared with ``value``."""

import numpy as np

from harness.reference import OPS


def branches(node: dict, tier: str, present) -> set[str]:
    c = node["collection"]
    return {f"n{c}", f"{c}_{node.get('var', 'pt')}"} | {
        f"{c}_{cut['var']}" for cut in node.get("object_cuts", [])
    }


def evaluate(sel, tier: str, node: dict):
    c = sel.cols.columns
    coll = node["collection"]
    ev = sel.cols.event_of(coll)
    ok = np.ones(len(ev), dtype=bool)
    for cut in node.get("object_cuts", []):
        ok &= OPS[cut["op"]](c[f"{coll}_{cut['var']}"], cut["value"])
    vals = sel._f(c[f"{coll}_{node.get('var', 'pt')}"])
    off = sel.cols.offsets(f"n{coll}")
    slot = np.arange(len(ev)) - off[ev]
    ht = np.zeros(sel.cols.n_events, dtype=sel.dtype)
    for j in range(int(slot.max()) + 1 if len(slot) else 0):
        on = (slot == j) & ok
        ht[ev[on]] = (ht[ev[on]] + vals[on]).astype(sel.dtype)
    v = node["value"]
    q = ht.astype(np.float64)
    return OPS[node["op"]](ht, sel.dtype(v)), np.abs(q - v) / abs(v)
