"""Host seconds densifying predicate and cascade kernel inputs
(``stage_inputs`` spans opened inside the window) per million input
events delivered in it."""


def read(run):
    lo, hi = run.t0 * 1e6, run.t_cut * 1e6
    s = sum(
        e["dur"] / 1e6
        for e in run.spans
        if e["cat"] == "stage_inputs" and lo <= e["ts"] < hi
    )
    if not s or not run.events_in_window:
        return None
    return s / (run.events_in_window / 1e6)
