"""The basket-decode kernel's share of its roofline, in percent: the
least time the chip's memory bandwidth allows for the bytes it decoded
(``harness.work``), over its device time in the trace."""

from harness.work import decode_bytes


def read(run):
    t = run.device_trace.kernel_s(run.kernels["decode"])
    work = decode_bytes(run)
    if not t or work is None:
        return None
    return 100.0 * work / run.peaks["hbm_bytes_per_s"] / t
