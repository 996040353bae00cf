"""Share of the traced window in which no operation ran on the device."""


def read(run):
    dt = run.device_trace
    return 1.0 - dt.busy_s() / dt.window_s
