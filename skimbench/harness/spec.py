"""What a cell is made of, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration's
file is the one ``BENCHMARK.json`` gives; the traffic mix is
``traffic/<traffic>.json``, each query template it uses is
``templates/<template>.json`` and each metric is read by
``metrics/<metric>.py``.  A configuration brings the rest of what it
needs as new files too: the reference evaluates each selection node by
``rules/<type>.py`` (``harness/reference.py``), and the generator draws
the fields its ``store`` declares (``harness/gen.py``).  Nothing here
knows any cell, configuration, traffic mix, node type or metric by name.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


@dataclass
class Metric:
    name: str
    unit: str
    end_to_end: bool
    reader: object = None  # the module of metrics/<name>.py


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    templates: dict  # template name -> query document
    metrics: list = field(default_factory=list)  # every Metric of this cell


def load_module(path: str, name: str):
    """The Python file at ``path``, imported as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reader(name: str):
    return load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"), f"skimbench_metric_{name}")


def load_cell(workload: str, bench_path: str | None = None) -> Cell:
    """Resolve one workload of ``BENCHMARK.json`` into a :class:`Cell`."""
    bench = _load_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = _load_json(os.path.join(BENCH_DIR, "traffic", f"{w['traffic']}.json"))
    templates = {
        t: _load_json(os.path.join(BENCH_DIR, "templates", f"{t}.json"))
        for t in traffic["tenant_templates"]
    }
    metrics = []
    for group, e2e in (("end_to_end", True), ("per_layer", False)):
        for m in bench[group]:
            if workload in m.get("workloads", [workload]):
                metrics.append(Metric(m["name"], m["unit"], e2e, _reader(m["name"])))
    return Cell(workload, int(w["chips"]), config, traffic, templates, metrics)


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind missing from the table is an
    error, never a default."""
    table = _load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]
