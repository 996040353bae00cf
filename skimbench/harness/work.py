"""Work a kernel family does in the traced window, counted from what it
was given, not from the padded shapes the program hands the device.

Predicate kernels: for every cascade stage that ran over a basket window
of a job, the decoded bytes of the branches that stage's selection node
reads over that window (from the generated columns).  Decode kernel:
the decoded bytes the store's decode cache reports as missed, which all
decode on the device when the tier is ``device`` and no basket went to
the host; the compressed plane bytes in are not counted, because the
program counts the compressed size of the baskets it fetches and not of
those it decodes, so this share is a lower bound.

The branches a stage reads come from its node type's rule
(``rules/<type>.py``), so a configuration's new node type is counted
with no edit here.  A later configuration's own kernel roofline is a new
``metrics/<name>.py`` that passes its own custom-call pattern to
``run.device_trace.kernel_s``, as ``kernels.predicate_roofline`` passes
``kernels.json``'s.
"""

from __future__ import annotations

from harness.reference import node_branches, selection_nodes


def _window_spans(run):
    """``(job_id, window_index, [stage indices])`` of every job-tree
    window span that opened inside the traced window."""
    lo, hi = run.t0 * 1e6, run.t_cut * 1e6
    windows, stages = {}, {}
    for e in run.spans:
        if e["pid"] >= 10_000:  # coalesced passes: one tree for many jobs
            continue
        key = (e["pid"], e["args"]["sid"])
        if e["cat"] == "window" and lo <= e["ts"] < hi:
            windows[key] = e["args"]["index"]
        elif e["cat"] == "cascade_stage":
            stages.setdefault((e["pid"], e["args"]["parent"]), []).append(e["args"]["stage"])
    return [(pid, wi, stages.get((pid, sid), [])) for (pid, sid), wi in windows.items()]


def predicate_bytes(run) -> int | None:
    docs = {r.job.job_id: r.doc for r in run.records}
    chunk = run.cell.config["store"]["basket_events"]
    n = run.columns.n_events
    present = run.columns.columns
    total, seen = 0, False
    for pid, wi, stages in _window_spans(run):
        nodes = selection_nodes(docs[pid])
        start, stop = wi * chunk, min((wi + 1) * chunk, n)
        for si in stages:
            tier, node = nodes[si]
            names = sorted(node_branches(node, tier, present))
            total += run.columns.window_bytes(names, start, stop)
            seen = True
    return total if seen else None


def decode_bytes(run) -> int | None:
    a, b = run.decode_at_open, run.decode_at_cut
    if b.get("backend") != "device" or b["host_baskets"] != a["host_baskets"]:
        return None
    out = b["miss_bytes"] - a["miss_bytes"]
    return out if out > 0 else None
