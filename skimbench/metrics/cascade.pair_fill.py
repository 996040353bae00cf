"""Share of the all-pairs kernel's slot pairs that hold two real
objects: the ``pairs_live`` over the ``pair_slots`` of every
``device_launch`` span opened inside the window.  A launch of a program
with a ``pair_mass`` group counts K(K−1)/2 slot pairs for every event row
it launches, padding included, and n(n−1)/2 live pairs for every real
event with n objects; other launches carry neither count."""


def read(run):
    lo, hi = run.t0 * 1e6, run.t_cut * 1e6
    live = slots = 0
    for e in run.spans:
        if e["cat"] == "device_launch" and lo <= e["ts"] < hi:
            live += e["args"].get("pairs_live", 0)
            slots += e["args"].get("pair_slots", 0)
    if not slots:
        return None
    return live / slots
