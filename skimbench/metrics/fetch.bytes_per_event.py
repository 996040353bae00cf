"""Compressed bytes the store fetched per input event, from the
``FetchStats`` of every job that ended DONE."""


def read(run):
    fetched = events = 0
    for r in run.records:
        if r.job.state == "DONE" and r.job.result is not None:
            fetched += r.job.result.stats.bytes_fetched
            events += r.job.result.n_input
    return fetched / events if events else None
