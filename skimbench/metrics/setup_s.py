"""Seconds from process start to the first timed submission: JAX start,
data generation and ingest, compile-cache loads and warm-up."""


def read(run):
    return run.setup_s
