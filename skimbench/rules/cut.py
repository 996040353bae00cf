"""``cut``: a flat branch compared with a threshold.  Every
``preselection`` node is one, and so is an ``event`` node without a
``type``."""

from harness.reference import OPS


def branches(node: dict, tier: str, present) -> set[str]:
    return {node["branch"]}


def evaluate(sel, tier: str, node: dict):
    return OPS[node["op"]](sel.cols.columns[node["branch"]], node["value"]), None
