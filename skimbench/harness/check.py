"""The comparison that decides ``correct``.

Each job's answer, as the service streamed it (its window partials:
survivor rows of every output branch), is held against the plain
reference (:mod:`harness.reference`) over the same generated columns.
Four numbers are compared, each with the limit the configuration
states:

``jobs_not_done``
    jobs due in the window that did not end DONE (failed, rejected, or
    never finished within the drain allowance);
``windows_not_once``
    DONE jobs whose streamed windows do not tile the file exactly once;
``column_mismatches``
    output cells of events both sides keep that differ bit for bit,
    plus output branches missing or extra, duplicated survivor rows and
    partials whose row count disagrees with their ``n_passed``;
``flip_margin_max``
    over events that one side keeps and the other drops, the largest
    relative distance from its cut edge that explains the flip: a
    dropped survivor is explained by its nearest floating-point node, a
    kept reject needs every failing node to be a floating-point node
    near its edge.  An exact node (a stored value against a threshold,
    an object count, a trigger bit) explains nothing: its distance is
    infinite.  0 when the two sides keep the same events.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from harness.reference import Columns, Selection, output_branches

CHECKS = ("jobs_not_done", "windows_not_once", "column_mismatches", "flip_margin_max")


@dataclass
class Answer:
    """One job's answer: its end state, the windows it streamed, and the
    concatenated survivor columns."""

    doc: dict
    state: str
    windows: list = field(default_factory=list)
    cols: dict = field(default_factory=dict)
    n_passed: int = 0


def from_partials(doc: dict, state: str, partials) -> Answer:
    """An :class:`Answer` from a job's streamed partial results."""
    cols: dict[str, list] = {}
    n = 0
    windows = []
    for p in partials:
        windows.append((p.start, p.stop))
        n += p.n_passed
        for name, arr in p.cols.items():
            cols.setdefault(name, []).append(np.asarray(arr))
    return Answer(
        doc, state, windows, {k: np.concatenate(v) for k, v in cols.items()}, n
    )


def _bits(a: np.ndarray) -> np.ndarray:
    return a.view(f"u{a.dtype.itemsize}") if a.dtype.itemsize in (1, 2, 4, 8) else a


def _cell_mismatches(got: np.ndarray, want: np.ndarray) -> int:
    if got.dtype != want.dtype or got.shape != want.shape:
        return max(len(got), len(want), 1)
    return int(np.count_nonzero(_bits(got) != _bits(want)))


def _flip_margins(sel: Selection, doc: dict, flips: np.ndarray, ref_pass: np.ndarray) -> np.ndarray:
    nodes = sel.nodes(doc)
    kept_by_ref = ref_pass[flips]
    drop = np.full(len(flips), np.inf)  # explains a dropped survivor
    keep = np.full(len(flips), -np.inf)  # explains a kept reject
    for mask, margin in nodes:
        m = np.full(len(flips), np.inf) if margin is None else margin[flips]
        drop = np.minimum(drop, m)
        keep = np.maximum(keep, np.where(mask[flips], -np.inf, m))
    return np.where(kept_by_ref, drop, keep)


def _columns(ans: Answer, doc: dict, cols: Columns, want_mask, got_events) -> int:
    bad = 0
    expected = output_branches(doc, cols)
    bad += len(set(ans.cols) - set(expected))
    common = want_mask.copy()
    got_mask = np.zeros_like(want_mask)
    got_mask[got_events] = True
    common &= got_mask
    n_common = int(common.sum())
    pos = np.searchsorted(got_events, np.flatnonzero(common))  # rows in ``ans``
    index: dict[str, tuple | None] = {}  # counts branch -> object rows, both sides

    def objects(cb: str):
        if cb not in index:
            got_counts = ans.cols.get(cb)
            if got_counts is None or len(got_counts) != len(got_events):
                index[cb] = None
                return None
            want_counts = cols.columns[cb][common].astype(np.int64)
            same = got_counts[pos].astype(np.int64) == want_counts
            goff = np.concatenate([[0], np.cumsum(got_counts.astype(np.int64))])
            n_obj = want_counts[same]
            rel = np.arange(int(n_obj.sum())) - np.repeat(np.cumsum(n_obj) - n_obj, n_obj)
            gidx = np.repeat(goff[pos[same]], n_obj) + rel
            widx = np.repeat(cols.offsets(cb)[np.flatnonzero(common)[same]], n_obj) + rel
            index[cb] = (int((~same).sum()), int(goff[-1]), gidx, widx)
        return index[cb]

    for name in expected:
        if name not in ans.cols:
            bad += max(n_common, 1)
            continue
        got = ans.cols[name]
        col = cols.columns[name]
        if name not in cols.jagged:
            if len(got) != len(got_events):
                bad += max(n_common, 1)
                continue
            bad += _cell_mismatches(got[pos], col[common])
            continue
        obj = objects(cols.jagged[name])
        if obj is None or obj[1] != len(got):
            bad += max(n_common, 1)
            continue
        counts_bad, _, gidx, widx = obj
        bad += counts_bad + _cell_mismatches(got[gidx], col[widx])
    return bad


def compare(answers: list[Answer], cols: Columns, sel: Selection | None = None) -> dict:
    """The four numbers of the module docstring over ``answers``."""
    sel = sel or Selection(cols)
    n = cols.n_events
    out = dict.fromkeys(CHECKS, 0)
    out["flip_margin_max"] = 0.0
    for ans in answers:
        if ans.state != "DONE":
            out["jobs_not_done"] += 1
            continue
        spans = sorted(ans.windows)
        tiles = [a for a, _ in spans] == [0] + [b for _, b in spans[:-1]] and (
            spans and spans[-1][1] == n
        )
        out["windows_not_once"] += 0 if tiles else 1
        want = sel.passed(ans.doc)
        events = ans.cols.get("event")
        if events is None:
            out["column_mismatches"] += max(ans.n_passed, 1)
            out["flip_margin_max"] = np.inf
            continue
        events = events.astype(np.int64)
        valid = (events >= 0) & (events < n)
        uniq = np.unique(events[valid])
        out["column_mismatches"] += int((~valid).sum()) + int(valid.sum() - len(uniq))
        out["column_mismatches"] += abs(len(events) - ans.n_passed)
        got_mask = np.zeros(n, dtype=bool)
        got_mask[uniq] = True
        flips = np.flatnonzero(got_mask != want)
        if len(flips):
            margins = _flip_margins(sel, ans.doc, flips, want)
            out["flip_margin_max"] = max(out["flip_margin_max"], float(margins.max()))
        if valid.all() and np.all(np.diff(events) > 0):
            out["column_mismatches"] += _columns(ans, ans.doc, cols, want, events)
        else:
            out["column_mismatches"] += max(len(events), 1)
    return out


def control_answers(docs: list[dict], cols: Columns, dtype) -> list[Answer]:
    """The reference in the precision ``dtype`` put in the program's place:
    derived quantities computed in ``dtype`` and float output values
    rounded through it.  One answer per query document."""
    from harness.reference import answer

    sel = Selection(cols, dtype=dtype)
    out = []
    for doc in docs:
        mask = sel.passed(doc)
        got = {
            k: v.astype(dtype).astype(v.dtype) if v.dtype.kind == "f" else v
            for k, v in answer(doc, mask, cols).items()
        }
        out.append(Answer(doc, "DONE", [(0, cols.n_events)], got, int(mask.sum())))
    return out


def verdict(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in CHECKS)
