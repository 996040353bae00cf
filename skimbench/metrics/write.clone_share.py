"""Share of the output baskets that the job-end write cloned from the
input store instead of encoding them again: Σ ``baskets_cloned`` over
Σ (``baskets_cloned`` + ``baskets_encoded``) of the ``write`` spans that
open inside the window.  ``None`` where no such span counts a basket, as
in a program whose ``write`` spans carry neither count."""


def read(run):
    lo, hi = run.t0 * 1e6, run.t_cut * 1e6
    cloned = total = 0
    for e in run.spans:
        if e["cat"] == "write" and lo <= e["ts"] < hi:
            c = e["args"].get("baskets_cloned", 0)
            cloned += c
            total += c + e["args"].get("baskets_encoded", 0)
    if not total:
        return None
    return cloned / total
