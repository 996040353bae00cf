"""NanoAOD-like event columns, generated from a seed.

The benchmark's own generator, grown from the repository's synthetic one
(``repro.data.synth.make_nanoaod_like``), so that the data a cell runs on
cannot move when the program changes.  It returns plain NumPy columns;
the harness hands them to the program's ``EventStore.from_arrays`` (the
ingest under test) and keeps them for the plain reference.

The configuration's ``store`` states the file's shape, branch by branch
group:

``collections``
    ``[{"name", "mean", "fields"}]``: a jagged collection with Poisson
    multiplicity ``mean`` per event, its counts branch ``n<name>`` and
    ``fields`` value branches ``<name>_<field>``.
``flat``
    ``[{"prefix", "fields", "kind"}]``: ``fields`` flat branches
    ``<prefix>_<field>``; ``kind`` is ``"ids"`` (run, luminosityBlock and
    event, which carry no prefix), ``"trigger"`` (bits, rate 0.02),
    ``"flag"`` (bits, rate 0.99) or ``"value"`` (the default).

A collection may also give ``"min"``, a multiplicity floor: its counts
are then ``min + Poisson(mean - min)``, so ``mean`` stays the mean.

A group's first fields carry the names the query templates read; the
rest are ``v<k>``, and by ``k`` a float32 value, a small int32 or a bit,
as NanoAOD mixes them.  A collection or flat group states its named
fields itself with ``"named"``, an ordered list of
``{"field", "dist", <params>, "dtype"}`` counted within ``fields``;
``dist`` is one of

``exponential``  ``scale``, ``offset`` (default 0): ``offset + Exp(scale)``
``uniform``      ``low``, ``high``
``abs_normal``   ``loc``, ``scale``: ``|Normal(loc, scale)|``
``choice``       ``values``: each equally likely
``bernoulli``    ``p``: true with probability ``p``
``beta``         ``a``, ``b``
``poisson``      ``lam``

and ``dtype`` a NumPy type name (``float32``, ``int32``, ``bool``).  A
group that states none takes ``NAMED``'s fields and their fixed draws:
Electron, Muon and Jet keep the repository generator's multiplicities
and distributions.  Lumi blocks hold 1,000 events.
"""

from __future__ import annotations

import numpy as np

#: the named leading fields of a group that declares none, as the query
#: templates read them
NAMED = {
    "Electron": ("pt", "eta", "phi", "mass", "charge", "mvaId"),
    "Muon": ("pt", "eta", "phi", "mass", "charge", "tightId"),
    "Jet": ("pt", "eta", "phi", "mass", "btagDeepB"),
    "MET": ("pt", "phi"),
    "PV": ("npvs",),
    "HLT": (
        "IsoMu24",
        "Ele32_WPTight_Gsf",
        "PFMET120_PFMHT120_IDTight",
        "DoubleEle25_CaloIdL_MW",
        "Mu17_TrkIsoVVL_Mu8_TrkIsoVVL",
    ),
}

RUN_NUMBER = 362_104
EVENTS_PER_LUMI_BLOCK = 1_000


def _named(rng: np.random.Generator, group: str, var: str, n: int) -> np.ndarray:
    if group == "MET":
        if var == "pt":
            return (rng.exponential(30.0, n) + 1.0).astype(np.float32)
        return rng.uniform(-np.pi, np.pi, n).astype(np.float32)
    if group == "PV":
        return rng.poisson(35.0, n).astype(np.int32)
    if group == "HLT":
        return rng.random(n, dtype=np.float32) < 0.15
    if var == "pt":
        return (rng.exponential(25.0, n) + 3.0).astype(np.float32)
    if var == "eta":
        return rng.uniform(-2.5, 2.5, n).astype(np.float32)
    if var == "phi":
        return rng.uniform(-np.pi, np.pi, n).astype(np.float32)
    if var == "mass":
        return np.abs(rng.normal(5.0, 3.0, n)).astype(np.float32)
    if var == "charge":
        return rng.choice(np.array([-1, 1], dtype=np.int32), n)
    if var in ("mvaId", "tightId"):
        return rng.random(n) > 0.3
    if var == "btagDeepB":
        return rng.beta(0.5, 2.0, n).astype(np.float32)
    raise ValueError(f"no generator for {group}_{var}")


def _generic(rng: np.random.Generator, kind: str, k: int, n: int) -> np.ndarray:
    if kind == "trigger":
        return rng.random(n, dtype=np.float32) < 0.02
    if kind == "flag":
        return rng.random(n, dtype=np.float32) < 0.99
    if k % 6 == 3:
        return rng.integers(-1, 8, n, dtype=np.int32)
    if k % 6 == 5:
        return rng.random(n, dtype=np.float32) < 0.5
    return rng.standard_normal(n, dtype=np.float32)


#: the declared draws, ``dist`` -> values from ``(rng, field, n)``
DISTS = {
    "exponential": lambda rng, d, n: rng.exponential(d["scale"], n) + d.get("offset", 0.0),
    "uniform": lambda rng, d, n: rng.uniform(d["low"], d["high"], n),
    "abs_normal": lambda rng, d, n: np.abs(rng.normal(d["loc"], d["scale"], n)),
    "choice": lambda rng, d, n: rng.choice(np.asarray(d["values"]), n),
    "bernoulli": lambda rng, d, n: rng.random(n) < d["p"],
    "beta": lambda rng, d, n: rng.beta(d["a"], d["b"], n),
    "poisson": lambda rng, d, n: rng.poisson(d["lam"], n),
}


def _declared(rng: np.random.Generator, field: dict, n: int) -> np.ndarray:
    if field["dist"] not in DISTS:
        raise ValueError(
            f"field {field['field']!r}: unknown dist {field['dist']!r}; known: {sorted(DISTS)}"
        )
    return DISTS[field["dist"]](rng, field, n).astype(field["dtype"])


def _group(rng: np.random.Generator, name: str, group: dict, kind: str, n: int):
    """``(field, values)`` of a group's fields in order: its declared
    ``named`` fields, or ``NAMED``'s, then ``v<k>``."""
    declared = group.get("named")
    named = [f["field"] for f in declared] if declared is not None else NAMED.get(name, ())
    if declared is not None and len(declared) > group["fields"]:
        raise ValueError(f"{name}: {len(declared)} fields named, {group['fields']} in all")
    for k in range(group["fields"]):
        if k >= len(named):
            yield f"v{k:02d}", _generic(rng, kind, k, n)
        elif declared is not None:
            yield named[k], _declared(rng, declared[k], n)
        else:
            yield named[k], _named(rng, name, named[k], n)


def nanoaod_columns(store: dict, seed: int) -> tuple[dict[str, np.ndarray], dict[str, str]]:
    """``(columns, jagged)`` for the configuration's ``store``: flat
    columns by branch name, and for each jagged value branch the name of
    its counts branch.  Jagged entries hold the flattened values of all
    events in order."""
    n_events = store["n_events"]
    rng = np.random.default_rng(seed)
    columns: dict[str, np.ndarray] = {}
    jagged: dict[str, str] = {}
    for coll in store["collections"]:
        name, floor = coll["name"], coll.get("min", 0)
        counts = (floor + rng.poisson(coll["mean"] - floor, n_events)).astype(np.int32)
        columns[f"n{name}"] = counts
        for var, values in _group(rng, name, coll, "value", int(counts.sum())):
            columns[f"{name}_{var}"] = values
            jagged[f"{name}_{var}"] = f"n{name}"
    for group in store["flat"]:
        prefix, kind = group["prefix"], group.get("kind", "value")
        if kind == "ids":
            columns["run"] = np.full(n_events, RUN_NUMBER, dtype=np.int32)
            columns["luminosityBlock"] = (
                np.arange(n_events) // EVENTS_PER_LUMI_BLOCK
            ).astype(np.int32)
            columns["event"] = np.arange(n_events, dtype=np.int32)
            continue
        for var, values in _group(rng, prefix, group, kind, n_events):
            columns[f"{prefix}_{var}"] = values
    return columns, jagged
