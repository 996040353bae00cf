"""The predicate kernels' share of their roofline, in percent: the
least time the chip's memory bandwidth allows for the decoded bytes of
the filter branches they evaluated (``harness.work``), over their device
time in the trace.  The operations are a few per byte, so bandwidth
bounds them."""

from harness.work import predicate_bytes


def read(run):
    t = run.device_trace.kernel_s(run.kernels["predicate"])
    work = predicate_bytes(run)
    if not t or work is None:
        return None
    return 100.0 * work / run.peaks["hbm_bytes_per_s"] / t
