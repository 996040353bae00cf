"""Bytes uploaded host to device per input event delivered in the window:
the ``h2d_bytes`` of every ``device_launch`` span opened inside it."""


def read(run):
    lo, hi = run.t0 * 1e6, run.t_cut * 1e6
    n = sum(
        e["args"].get("h2d_bytes", 0)
        for e in run.spans
        if e["cat"] == "device_launch" and lo <= e["ts"] < hi
    )
    if not n or not run.events_in_window:
        return None
    return n / run.events_in_window
