"""Blocking device read-backs (``device_wait`` spans) opened inside the
window, per million input events delivered in it."""


def read(run):
    lo, hi = run.t0 * 1e6, run.t_cut * 1e6
    n = sum(1 for e in run.spans if e["cat"] == "device_wait" and lo <= e["ts"] < hi)
    if not n or not run.events_in_window:
        return None
    return n / (run.events_in_window / 1e6)
