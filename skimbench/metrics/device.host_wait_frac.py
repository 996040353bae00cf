"""Share of the window the host spent blocked on device read-backs: the
summed duration of the ``device_wait`` spans opened inside the window,
over the window's seconds."""


def read(run):
    lo, hi = run.t0 * 1e6, run.t_cut * 1e6
    s = sum(
        e["dur"] / 1e6
        for e in run.spans
        if e["cat"] == "device_wait" and lo <= e["ts"] < hi
    )
    if not s or run.t_cut <= run.t0:
        return None
    return s / (run.t_cut - run.t0)
