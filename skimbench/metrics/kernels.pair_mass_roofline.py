"""The all-pairs mass kernel's share of its roofline, in percent: the
least time the chip allows for its work, over the device time of the
predicate custom-calls (``kernels.json``'s ``predicate`` pattern), which
in a cell whose only selection node is ``pair_mass`` are the pair
kernel's launches.

The least time is the larger of two bounds: the decoded bytes of the
filter branches (``harness.work.predicate_bytes``) at the memory
bandwidth, and the operations of the live pairs and objects at the bf16
peak.  Those are counted from the generated columns over every window a
``pair_mass`` stage ran on: ``OPS_PER_PAIR`` for each pair of real
objects (the pair mass from the per-slot terms, the window, the charge
product and the validity of both slots) and ``OPS_PER_SLOT`` for each
real object (its kinematic terms; exp, cos, sin and sqrt count one
each).  The kernel computes in float32 on the vector unit, whose peak is
far below the bf16 matrix peak that ``peaks.json`` holds, and it also
evaluates padding slots and compacts the survivors; so the share is a
lower bound."""

import numpy as np

from harness.reference import node_type, selection_nodes
from harness.work import _window_spans, predicate_bytes

OPS_PER_PAIR = 36
OPS_PER_SLOT = 17


def pair_ops(run) -> int | None:
    docs = {r.job.job_id: r.doc for r in run.records}
    chunk = run.cell.config["store"]["basket_events"]
    n = run.columns.n_events
    total, seen = 0, False
    for pid, wi, stages in _window_spans(run):
        nodes = selection_nodes(docs[pid])
        start, stop = wi * chunk, min((wi + 1) * chunk, n)
        for si in stages:
            tier, node = nodes[si]
            if node_type(tier, node) != "pair_mass":
                continue
            counts = run.columns.columns[f"n{node['collection']}"][start:stop].astype(np.int64)
            total += OPS_PER_PAIR * int((counts * (counts - 1) // 2).sum())
            total += OPS_PER_SLOT * int(counts.sum())
            seen = True
    return total if seen else None


def read(run):
    t = run.device_trace.kernel_s(run.kernels["predicate"])
    ops = pair_ops(run)
    work = predicate_bytes(run)
    if not t or ops is None or work is None:
        return None
    least = max(work / run.peaks["hbm_bytes_per_s"], ops / run.peaks["bf16_flop_per_s"])
    return 100.0 * least / t
