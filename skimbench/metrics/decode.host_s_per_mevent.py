"""Host seconds inside ``decode`` and ``decode_device`` spans per million
input events delivered in the window."""


def read(run):
    lo, hi = run.t0 * 1e6, run.t_cut * 1e6
    s = sum(
        e["dur"] / 1e6
        for e in run.spans
        if e["cat"] in ("decode", "decode_device") and lo <= e["ts"] < hi
    )
    if not s or not run.events_in_window:
        return None
    return s / (run.events_in_window / 1e6)
