"""``any``: the OR of boolean branches, an absent branch counting false."""

import numpy as np


def branches(node: dict, tier: str, present) -> set[str]:
    return {b for b in node["branches"] if b in present}


def evaluate(sel, tier: str, node: dict):
    c = sel.cols.columns
    mask = np.zeros(sel.cols.n_events, dtype=bool)
    for b in node["branches"]:
        if b in c:
            mask |= c[b].astype(bool)
    return mask, None
