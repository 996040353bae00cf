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

A group's first fields carry the names the query templates read
(``NAMED``); the rest are ``v<k>``, and by ``k`` a float32 value, a small
int32 or a bit, as NanoAOD mixes them.  Electron, Muon and Jet keep the
repository generator's multiplicities and distributions; lumi blocks hold
1,000 events.
"""

from __future__ import annotations

import numpy as np

#: the named leading fields of a group, as the query templates read them
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


def _fields(group: str, n_fields: int) -> list[tuple[int, str]]:
    named = NAMED.get(group, ())
    return [(k, named[k] if k < len(named) else f"v{k:02d}") for k in range(n_fields)]


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
        name = coll["name"]
        counts = rng.poisson(coll["mean"], n_events).astype(np.int32)
        total = int(counts.sum())
        columns[f"n{name}"] = counts
        for k, var in _fields(name, coll["fields"]):
            columns[f"{name}_{var}"] = (
                _named(rng, name, var, total)
                if k < len(NAMED.get(name, ()))
                else _generic(rng, "value", k, total)
            )
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
        for k, var in _fields(prefix, group["fields"]):
            columns[f"{prefix}_{var}"] = (
                _named(rng, prefix, var, n_events)
                if k < len(NAMED.get(prefix, ()))
                else _generic(rng, kind, k, n_events)
            )
    return columns, jagged
