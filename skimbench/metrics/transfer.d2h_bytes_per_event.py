"""Bytes read back device to host per input event delivered in the
window: the ``d2h_bytes`` of every ``device_wait`` span opened inside it."""


def read(run):
    lo, hi = run.t0 * 1e6, run.t_cut * 1e6
    n = sum(
        e["args"].get("d2h_bytes", 0)
        for e in run.spans
        if e["cat"] == "device_wait" and lo <= e["ts"] < hi
    )
    if not n or not run.events_in_window:
        return None
    return n / run.events_in_window
