"""The comparison that decides ``correct`` fails what it has to fail, in
every cell of ``BENCHMARK.json``:

* the control: the reference with HT, pair mass and delta R computed,
  and float outputs rounded, in bfloat16 (the precision below the
  configuration's float32), put in the program's place;
* a run of the harness with the timed path broken underneath: an
  answer altered where the service streams it (a survivor dropped, an
  output value changed), a window never streamed, a job that fails.

Sizes are small enough for the CPU; ``control.py`` runs the control at
the cells' own size.
"""

import json
import os
import time

import jax
import pytest

import control
from harness import runner, spec

CELLS = [w["name"] for w in json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))["workloads"]]


def small_cell(name: str, n_events: int = 20_000):
    cell = spec.load_cell(name)
    cell.config["store"]["n_events"] = n_events
    if cell.traffic.get("ranges"):
        cell.traffic["ranges"]["width"] = 2  # 2,000-event chunks
        cell.traffic["rate_per_s"] = 4.0
    return cell


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_stated_precision_passes(name):
    cell = small_cell(name, 50_000)
    low = control.control_run(cell, 2**33 + 3, 5.0, "bfloat16")
    assert low["failed_as_it_must"], low
    assert any(10 * c["limit"] < c["value"] for c in low["checks"].values()), low
    same = control.control_run(cell, 2**33 + 3, 5.0, "float32")
    assert not same["failed_as_it_must"], same


def _alter_first_survivor(wp):
    wp.cols = {k: v.copy() for k, v in wp.cols.items()}
    name = next(k for k, v in sorted(wp.cols.items()) if v.dtype.kind == "f" and len(v))
    wp.cols[name][0] += 1.0


def _drop_last_survivor(wp):
    keep = wp.n_passed - 1
    counts = {}
    for name in list(wp.cols):
        if name in wp.jagged:
            continue
        counts[name] = wp.cols[name][:keep]
    for name, cb in wp.jagged.items():
        n_obj = int(wp.cols[cb][:keep].sum())
        counts[name] = wp.cols[name][:n_obj]
    wp.cols = counts
    wp.n_passed = keep


FAULTS = {
    "answer_altered": ("column_mismatches", _alter_first_survivor),
    "survivor_dropped": ("flip_margin_max", _drop_last_survivor),
}


def _run(cell, seconds=1.5):
    return runner.execute(cell, 2**33 + 5, seconds, False, jax.devices(), time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_answer_is_not_correct(name, fault, monkeypatch):
    from repro.serve import service

    check_name, mutate = FAULTS[fault]
    orig = service.SkimService._append_partial
    done = []

    def broken(self, job, wp):
        if not done and wp.n_passed > 1 and job.tenant.startswith("tenant"):
            mutate(wp)
            done.append(job.job_id)
        return orig(self, job, wp)

    monkeypatch.setattr(service.SkimService, "_append_partial", broken)
    res = _run(small_cell(name))
    assert done, "the fault never fired"
    assert res["correct"] is False
    c = res["checks"][check_name]
    assert c["value"] > c["limit"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_window_never_streamed_is_not_correct(name, monkeypatch):
    from repro.serve import service

    orig = service.SkimService._append_partial
    done = []

    def skip(self, job, wp):
        if not done and job.tenant.startswith("tenant"):
            done.append(job.job_id)
            return None
        return orig(self, job, wp)

    monkeypatch.setattr(service.SkimService, "_append_partial", skip)
    res = _run(small_cell(name))
    assert done and res["correct"] is False
    assert res["checks"]["windows_not_once"]["value"] >= 1


@pytest.mark.parametrize("name", CELLS)
def test_failed_job_is_not_correct(name, monkeypatch):
    from repro.serve import service

    orig = service.SkimService._advance
    done = []

    def fail(self, run):
        if not done and any(j.tenant.startswith("tenant") for j in run.jobs):
            done.append(True)
            self._fail(run, RuntimeError("injected"))
            return None
        return orig(self, run)

    monkeypatch.setattr(service.SkimService, "_advance", fail)
    res = _run(small_cell(name))
    assert done and res["correct"] is False
    assert res["failed"] >= 1
    assert res["checks"]["jobs_not_done"]["value"] >= 1
