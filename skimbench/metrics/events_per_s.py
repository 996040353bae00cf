"""Input events of every basket window whose partial the service
delivered inside the window (scanned, pruned or accepted alike), over
the window's seconds."""


def read(run):
    return run.events_in_window / run.seconds
