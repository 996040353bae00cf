"""The one traffic generator: a traffic file's parameters become jobs.

A traffic file (``traffic/<name>.json``) holds:

``loop``
    ``"closed"``: ``clients`` clients each submit their next job the
    moment the previous one is DONE.  ``"open"``: jobs arrive on a
    Poisson schedule at ``rate_per_s``, whatever the service does.
``tenant_templates``
    the query template of each tenant: tenant ``i`` runs
    ``tenant_templates[i % len]``.
``tenant_zipf_s``
    the Zipf exponent of tenant popularity over the configuration's
    ``tenants`` (tenant 0 most popular; 0 is uniform).
``ranges`` (optional)
    ``{"branch", "width", "count", "zipf_s"}``: each job covers one of
    ``count`` ranges ``[k * width, (k + 1) * width)`` of ``branch``,
    drawn Zipf over ``k``, added to the template as two preselection
    cuts.  Without it a job covers the whole file.
``base_seed``
    the seed of the job mix and the arrival gaps: the schedule is part
    of the traffic mix, the same in every run.  A run's ``--seed`` makes
    the data.  (Permuting the schedule per seed would change the tail
    of a few dozen queued jobs more than any change to the code.)
"""

from __future__ import annotations

import copy
import itertools
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Job:
    tenant: int
    template: str
    range_index: int | None
    due: float = 0.0  # seconds after the window opens (open loop)


def _zipf(rng: np.random.Generator, n: int, s: float, size: int) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return rng.choice(n, size=size, p=p / p.sum())


def query(traffic: dict, templates: dict, job: Job) -> dict:
    """The query document a job submits."""
    doc = copy.deepcopy(templates[job.template])
    r = traffic.get("ranges")
    if r is not None and job.range_index is not None:
        lo = job.range_index * r["width"]
        pre = doc.setdefault("selection", {}).setdefault("preselection", [])
        pre += [
            {"branch": r["branch"], "op": ">=", "value": lo},
            {"branch": r["branch"], "op": "<", "value": lo + r["width"]},
        ]
    return doc


def _draw(traffic: dict, n_tenants: int, rng, size: int) -> list[Job]:
    tt = traffic["tenant_templates"]
    tenants = _zipf(rng, n_tenants, traffic.get("tenant_zipf_s", 0.0), size)
    r = traffic.get("ranges")
    ranges = _zipf(rng, r["count"], r["zipf_s"], size) if r else [None] * size
    return [
        Job(int(t), tt[int(t) % len(tt)], None if k is None else int(k))
        for t, k in zip(tenants, ranges)
    ]


def open_schedule(traffic: dict, n_tenants: int, seconds: float) -> list[Job]:
    """The open loop's jobs, due times ascending, all due before
    ``seconds``."""
    base = np.random.default_rng(traffic["base_seed"])
    rate = traffic["rate_per_s"]
    gaps = base.exponential(1.0 / rate, size=int(rate * seconds * 3) + 16)
    due = np.cumsum(gaps)
    n = int(np.searchsorted(due, seconds))
    jobs = _draw(traffic, n_tenants, base, n)
    return [Job(j.tenant, j.template, j.range_index, float(t)) for j, t in zip(jobs, due)]


def closed_jobs(traffic: dict, n_tenants: int):
    """The closed loop's endless job stream for one client."""
    base = np.random.default_rng(traffic["base_seed"])
    while True:
        yield from _draw(traffic, n_tenants, base, 64)


def warmup_jobs(traffic: dict, n_tenants: int, seconds: float) -> list[Job]:
    """One job for each (template, range) pair the window can submit: the
    pairs of the open loop's schedule, or every pair for a closed loop,
    whose stream has no end."""
    if traffic["loop"] == "open":
        pairs = {(j.template, j.range_index): j.tenant
                 for j in open_schedule(traffic, n_tenants, seconds)}
    else:
        r = traffic.get("ranges")
        ks = range(r["count"]) if r else [None]
        tt = list(dict.fromkeys(traffic["tenant_templates"]))
        pairs = {(t, k): i for (i, t), k in itertools.product(enumerate(tt), ks)}
    return [Job(i, t, k) for (t, k), i in pairs.items()]
