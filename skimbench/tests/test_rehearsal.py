"""CPU rehearsal of the harness: every cell of ``BENCHMARK.json`` driven
end to end at about 3,000 events, with the Pallas kernels in interpret
mode and the device decode's jitted mirror, and the result line checked
for its shape.  Without a TPU the command itself exits non-zero and
prints no result."""

import json
import os
import subprocess
import sys
import time

import jax
import pytest

from harness import runner, spec

ROOT = spec.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_no_tpu_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cmd = [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
           "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def _device_decode(monkeypatch):
    from repro.data import store as store_mod

    orig = store_mod.EventStore.from_arrays.__func__

    def device_decode(cls, *a, **kw):
        kw["decode_backend"] = "device"
        return orig(cls, *a, **kw)

    monkeypatch.setattr(store_mod.EventStore, "from_arrays", classmethod(device_decode))


@pytest.mark.parametrize("name", CELLS)
def test_result_line_shape(name, monkeypatch, capsys):
    _device_decode(monkeypatch)
    cell = spec.load_cell(name)
    cell.config["store"]["n_events"] = 3_000
    cell.config["engine"] = {**cell.config.get("engine", {}), "fused_backend": "pallas"}
    res = runner.execute(cell, 2**33 + 9, 1.0, False, jax.devices(), time.perf_counter())
    runner.emit(res)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    want = {m.name for m in cell.metrics if m.end_to_end}
    assert set(line["metrics"]) == want
    for m in cell.metrics:
        if m.end_to_end:
            assert line["metrics"][m.name]["unit"] == m.unit
            assert line["metrics"][m.name]["value"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") and "limit" in t for t in tail)


def test_open_loop_shared_scan(monkeypatch):
    """The generator's open loop, tenants and ranges, which a traffic file
    can ask for, through a coalescing service and every query template."""
    _device_decode(monkeypatch)
    cell = spec.load_cell(CELLS[0])
    cell.config["store"]["n_events"] = 3_000
    cell.config["service"] = {"batching": True, "tenants": 4}
    names = sorted(f[:-5] for f in os.listdir(os.path.join(spec.BENCH_DIR, "templates")))
    cell.templates = {t: json.load(open(os.path.join(spec.BENCH_DIR, "templates", f"{t}.json")))
                      for t in names}
    cell.traffic = {"loop": "open", "rate_per_s": 6.0, "base_seed": 7, "tenant_templates": names,
                    "tenant_zipf_s": 1.1,
                    "ranges": {"branch": "luminosityBlock", "width": 1, "count": 3, "zipf_s": 1.1}}
    res = runner.execute(cell, 2**33 + 11, 1.5, False, jax.devices(), time.perf_counter())
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert res["correct"] is True, res["checks"]
