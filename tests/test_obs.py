"""Unified trace/metrics layer (DESIGN.md §13).

Pins the PR-7 observability contracts:

  * span trees: parenting, begin/end stack discipline, adoption;
  * byte-deterministic Chrome-trace export under a ManualClock;
  * a service job's complete lifecycle span tree
    (admission -> queue -> execution -> settle);
  * cluster scatter-gather: node spans adopted exactly once, under the
    coordinator's merge span;
  * the versioned SkimReport + its extras compatibility shim;
  * priced-vs-observed calibration feeding back into admission pricing;
  * unified cache metrics and the result-cache replacement fix;
  * the no-op tracer changes nothing about results.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from repro.cluster import SkimResultCache, build_cluster
from repro.core.engine import SkimEngine, run_skim
from repro.core.plan import estimate_plan_bytes, stage_kind
from repro.core.planner import plan_skim
from repro.core.query import parse_query
from repro.data.synth import make_nanoaod_like
from repro.obs import (
    NULL_TRACER,
    MetricsRegistry,
    SkimReport,
    Tracer,
    chrome_trace,
    collect_cache_metrics,
    make_extras,
    trace_json,
    unified_cache_report,
)
from repro.serve import ManualClock, SkimService
from repro.serve.service import ClusterBackend, EngineBackend
from tests.test_query import QUERY

ROOT = Path(__file__).resolve().parents[1]

N_EVENTS = 10_000
BASKET = 2048


def _store(seed: int = 11):
    return make_nanoaod_like(
        n_events=N_EVENTS, basket_events=BASKET, seed=seed
    )


@pytest.fixture(scope="module")
def store():
    return _store()


# ---------------------------------------------------------------------------
# tracer basics
# ---------------------------------------------------------------------------


def test_tracer_nesting_and_stack():
    tr = Tracer(clock=ManualClock())
    a = tr.begin("a", kind="query")
    b = tr.begin("b", kind="window")
    with tr.span("c", kind="fetch") as sp:
        sp["bytes"] = 7
    tr.end(b)
    tr.end(a, n_passed=3)
    spans = {s.name: s for s in tr.spans()}
    assert spans["a"].parent is None
    assert spans["b"].parent == a
    assert spans["c"].parent == b
    assert spans["c"].attrs["bytes"] == 7
    assert spans["a"].attrs["n_passed"] == 3
    # ending a parent pops dangling children off the stack
    d = tr.begin("d", kind="query")
    tr.begin("e", kind="window")
    tr.end(d)
    f = tr.begin("f", kind="query")
    assert tr.get(f).parent is None


def test_tracer_adopt_reparents_exactly_once():
    child = Tracer(clock=ManualClock())
    r = child.begin("node_query", kind="query")
    child.end(child.begin("w0", kind="window"))
    child.end(r)

    parent = Tracer(clock=ManualClock())
    shard = parent.begin("shard[0]", kind="shard")
    n = parent.adopt(child.spans(), parent=shard)
    parent.end(shard)
    assert n == 2
    by_name = {s.name: s for s in parent.spans()}
    assert by_name["node_query"].parent == shard
    # internal parent links remapped to the NEW ids, not the child's
    assert by_name["w0"].parent == by_name["node_query"].sid


def test_null_tracer_is_inert():
    assert NULL_TRACER.begin("x") == 0
    NULL_TRACER.end(0, anything=1)
    with NULL_TRACER.span("y") as sp:
        sp["k"] = "v"
    assert NULL_TRACER.spans() == []
    assert NULL_TRACER.adopt([1, 2, 3]) == 0


def test_chrome_trace_shape():
    tr = Tracer(clock=ManualClock())
    tr.end(tr.begin("q", kind="query"))
    doc = chrome_trace([(3, "job-3", tr)])
    events = doc["traceEvents"]
    assert events[0]["ph"] == "M"  # process_name metadata
    assert events[0]["args"]["name"] == "job-3"
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == 1 and xs[0]["pid"] == 3 and xs[0]["cat"] == "query"
    json.loads(trace_json(doc))  # serializes to valid JSON


def test_trace_json_coerces_numpy():
    tr = Tracer(clock=ManualClock())
    sid = tr.begin("q", kind="query")
    tr.end(sid, n=np.int64(5), b=np.bool_(True))
    payload = trace_json(tr.chrome_trace())
    args = json.loads(payload)["traceEvents"][1]["args"]
    assert args["n"] == 5


# ---------------------------------------------------------------------------
# deterministic engine traces
# ---------------------------------------------------------------------------


def _traced_run(seed: int = 11) -> tuple[Tracer, object]:
    st = _store(seed)
    tr = Tracer(clock=ManualClock())
    eng = SkimEngine(st, chunk_events=BASKET, pipeline=False)
    res = eng.run(QUERY, mode="near_data", tracer=tr)
    return tr, res


def test_engine_trace_deterministic_bytes():
    tr1, res1 = _traced_run()
    tr2, res2 = _traced_run()
    assert res1.n_passed == res2.n_passed
    j1 = trace_json(chrome_trace([(0, "q", tr1)]))
    j2 = trace_json(chrome_trace([(0, "q", tr2)]))
    assert j1 == j2  # byte-identical under the manual clock


def test_engine_trace_covers_the_pipeline():
    tr, res = _traced_run()
    kinds = {s.kind for s in tr.spans()}
    assert {"query", "plan", "window", "fetch", "decode"} <= kinds
    roots = tr.roots()
    assert len(roots) == 1 and roots[0].kind == "query"
    # one window span per executed window
    windows = [s for s in tr.spans() if s.kind == "window"]
    assert len(windows) == len(res.extras["window_rows"])
    assert all(s.parent == roots[0].sid for s in windows)


def test_null_tracer_equivalent_result(store):
    a = run_skim(store, QUERY, mode="near_data", fused=True, pipeline=False)
    tr = Tracer(clock=ManualClock())
    eng = SkimEngine(store, chunk_events=BASKET, pipeline=False)
    b = eng.run(QUERY, mode="near_data", tracer=tr)
    assert a.n_passed == b.n_passed
    assert a.stats.bytes_fetched == b.stats.bytes_fetched


# ---------------------------------------------------------------------------
# service lifecycle span tree
# ---------------------------------------------------------------------------


def _traced_service(seed: int = 11, **kw) -> SkimService:
    return SkimService(
        EngineBackend(_store(seed)),
        clock=ManualClock(),
        tracing=True,
        **kw,
    )


def test_service_job_complete_span_tree():
    svc = _traced_service()
    job = svc.submit(QUERY, tenant="atlas")
    svc.run_until_idle()
    assert job.state == "DONE"
    tr = job.tracer
    kinds = {s.kind for s in tr.spans()}
    assert {
        "job", "admission", "queue", "query", "plan", "window", "settle"
    } <= kinds
    roots = tr.roots()
    assert len(roots) == 1 and roots[0].kind == "job"
    # lifecycle spans parent directly under the job root
    by_kind = {}
    for s in tr.spans():
        by_kind.setdefault(s.kind, []).append(s)
    for kind in ("admission", "queue", "settle", "query"):
        assert all(s.parent == roots[0].sid for s in by_kind[kind])
    assert by_kind["settle"][0].attrs["state"] == "DONE"
    assert by_kind["admission"][0].attrs["admitted"] is True
    # the export is valid JSON with one pid per job
    doc = svc.export_trace()
    json.loads(trace_json(doc))
    assert {e["pid"] for e in doc["traceEvents"]} == {job.job_id}


def test_service_rejected_job_traced():
    from repro.serve import TenantQuota

    svc = _traced_service(quotas={"t": TenantQuota(byte_budget=1)})
    job = svc.submit(QUERY, tenant="t")
    assert job.state == "REJECTED"
    spans = {s.kind: s for s in job.tracer.spans()}
    assert spans["admission"].attrs["admitted"] is False
    assert spans["job"].attrs["state"] == "REJECTED"
    assert svc.metrics.counter(
        "service_jobs_total", state="REJECTED", tenant="t"
    ) == 1


def test_service_drain_export_deterministic(tmp_path):
    def drain():
        svc = _traced_service(calibrate=True)
        for i in range(4):
            svc.submit(QUERY, tenant=f"t{i % 2}")
        svc.run_until_idle()
        return svc

    p = tmp_path / "trace.json"
    doc = drain().export_trace(str(p))
    on_disk = p.read_text()
    assert on_disk == trace_json(doc)
    assert trace_json(drain().export_trace()) == on_disk
    assert len({e["pid"] for e in doc["traceEvents"]}) == 4


def test_service_batch_drain_traced():
    svc = SkimService(
        EngineBackend(_store()),
        clock=ManualClock(),
        tracing=True,
        batching=True,
    )
    jobs = [svc.submit(QUERY, tenant=f"t{i}") for i in range(3)]
    svc.run_until_idle()
    assert all(j.state == "DONE" for j in jobs)
    doc = svc.export_trace()
    pids = {e["pid"] for e in doc["traceEvents"]}
    # three job pids + the shared batch pass at 10000
    assert pids == {1, 2, 3, 10_000}
    batch_events = [e for e in doc["traceEvents"] if e["pid"] == 10_000]
    assert any(e.get("cat") == "window" for e in batch_events)


# ---------------------------------------------------------------------------
# cluster scatter-gather re-parenting
# ---------------------------------------------------------------------------


def test_cluster_trace_adopts_each_node_exactly_once(store):
    cl = build_cluster(store, n_nodes=3)
    tr = Tracer(clock=ManualClock())
    res = cl.run(QUERY, tracer=tr)
    by_id = {s.sid: s for s in tr.spans()}
    roots = tr.roots()
    assert len(roots) == 1 and roots[0].name == "cluster_query"
    merges = [s for s in tr.spans() if s.kind == "merge"]
    assert len(merges) == 1 and merges[0].parent == roots[0].sid
    shards = [s for s in tr.spans() if s.kind == "shard"]
    assert len(shards) == 3
    assert all(s.parent == merges[0].sid for s in shards)
    # each node's root query span adopted exactly once, under its shard
    node_queries = [
        s for s in tr.spans() if s.kind == "query" and s.name == "query"
    ]
    assert len(node_queries) == len(res.responses) == 3
    assert sorted(by_id[s.parent].kind for s in node_queries) == [
        "shard", "shard", "shard"
    ]


def test_cluster_cached_responses_carry_no_trace(store):
    cache = SkimResultCache()
    cl = build_cluster(store, n_nodes=2, cache=cache)
    tr_cold = Tracer(clock=ManualClock())
    cl.run(QUERY, tracer=tr_cold)
    n_cold = len(tr_cold.spans())
    tr_warm = Tracer(clock=ManualClock())
    warm = cl.run(QUERY, tracer=tr_warm)
    assert warm.cache_hits == 2
    shards = [s for s in tr_warm.spans() if s.kind == "shard"]
    assert all(s.attrs["cached"] for s in shards)
    # no node spans re-adopted from the cached responses
    assert not any(
        s.kind == "query" and s.name == "query" for s in tr_warm.spans()
    )
    assert len(tr_warm.spans()) < n_cold


# ---------------------------------------------------------------------------
# SkimReport + extras compatibility shim
# ---------------------------------------------------------------------------


def test_skimreport_attached_and_extras_match(store):
    res = run_skim(store, QUERY, mode="near_data", fused=True, pipeline=False)
    assert isinstance(res.report, SkimReport)
    assert res.extras == res.report.legacy_extras()
    assert res.report.version == 1
    # the historical single-engine key set, exactly
    assert {
        "output_bytes", "fused", "pipelined", "window_rows",
        "pruned_windows", "prune", "phase1_bytes", "phase2_bytes",
        "overlap_total", "phase_wall_s",
    } <= set(res.extras)
    assert "shared_scan" not in res.extras
    assert "shard_pruned" not in res.extras


def test_skimreport_shared_scan_shim(store):
    eng = SkimEngine(store, chunk_events=BASKET)
    batch = eng.run_batch([QUERY, QUERY])
    for r in batch.results:
        assert isinstance(r.report, SkimReport)
        assert r.extras == r.report.legacy_extras()
        assert r.extras["shared_scan"] is True
        assert "phase1_bytes" not in r.extras  # tenants share the scan


def test_make_extras_rejects_unknown_keys():
    assert make_extras(output_bytes=1, tenant=0) == {
        "output_bytes": 1, "tenant": 0
    }
    with pytest.raises(KeyError):
        make_extras(totally_new_key=1)


# ---------------------------------------------------------------------------
# calibration: priced vs observed
# ---------------------------------------------------------------------------


def test_estimate_calibration_scales_stages(store):
    plan = plan_skim(
        parse_query(QUERY), store, window_events=BASKET, cascade=True
    )
    base = estimate_plan_bytes(plan, store, BASKET)
    kinds = set(base["per_stage_kinds"].values())
    assert kinds  # the cascade priced real stages
    half = estimate_plan_bytes(
        plan, store, BASKET, calibration={k: 0.5 for k in kinds}
    )
    assert half["phase1"] < base["phase1"]
    # ratios clamp at 20x: an absurd prior cannot blow the estimate up
    # 1000x (small slack for per-stage integer rounding)
    wild = estimate_plan_bytes(
        plan, store, BASKET, calibration={k: 1000.0 for k in kinds}
    )
    assert wild["phase1"] < base["phase1"] * 21


def test_stage_kind_taxonomy(store):
    plan = plan_skim(
        parse_query(QUERY), store, window_events=BASKET, cascade=True
    )
    kinds = {stage_kind(s) for s in plan.cascade.stages}
    known = {
        "cut", "trigger", "object", "ht", "mass", "deltaR", "expr",
        "const", "other",
    }
    assert kinds <= known


def test_metrics_registry_calibration_roundtrip():
    m = MetricsRegistry()
    m.record_price_ratio("cut", 100, 50)
    m.record_price_ratio("cut", 100, 70)
    m.record_price_ratio("trigger", 0, 10)
    summary = m.calibration_summary()
    assert summary["cut"]["n"] == 2
    assert summary["cut"]["ratio"] == pytest.approx(120 / 200)
    assert summary["trigger"]["ratio"] is None  # zero priced bytes
    priors = m.calibration_priors(min_samples=2)
    assert priors == {"cut": pytest.approx(0.6)}


def test_service_calibration_feedback():
    svc = _traced_service(calibrate=True)
    j1 = svc.submit(QUERY, tenant="a")
    svc.run_until_idle()
    summary = svc.calibration_summary()
    assert summary["total"]["observed_bytes"] == j1.result.stats.bytes_fetched
    priors = svc.metrics.calibration_priors()
    assert "total" in priors
    # the second submission prices THROUGH the accumulated priors
    j2 = svc.submit(QUERY, tenant="a")
    assert j2.estimate.est_bytes != j1.estimate.est_bytes


# ---------------------------------------------------------------------------
# metrics: registry + unified caches
# ---------------------------------------------------------------------------


def test_metrics_counters_gauges_histograms():
    m = MetricsRegistry()
    m.inc("jobs", state="DONE")
    m.inc("jobs", state="DONE")
    m.inc("jobs", state="FAILED")
    assert m.counter("jobs", state="DONE") == 2
    m.set_gauge("depth", 4)
    assert m.gauge("depth") == 4
    m.observe("wait_s", 1.0)
    m.observe("wait_s", 3.0)
    h = m.histogram("wait_s")
    assert h["count"] == 2 and h["sum"] == 4.0 and h["max"] == 3.0
    snap = m.snapshot()
    assert snap["counters"]["jobs{state=DONE}"] == 2


def test_service_metrics_recorded():
    svc = _traced_service()
    job = svc.submit(QUERY, tenant="atlas")
    svc.run_until_idle()
    m = svc.metrics
    assert m.counter("service_jobs_total", state="DONE", tenant="atlas") == 1
    assert m.histogram("service_queue_wait_s")["count"] == 1
    assert m.histogram("service_first_partial_s")["count"] == 1
    assert m.gauge("tenant_spent_bytes", tenant="atlas") == (
        job.result.stats.bytes_fetched
    )


def test_unified_cache_report_and_gauges():
    st = make_nanoaod_like(4_000, n_hlt=4, basket_events=1024)
    st.read_flat("MET_pt")
    st.read_flat("MET_pt")  # second read hits
    cache = SkimResultCache()
    cache.get("absent")
    report = unified_cache_report(store=st, result_cache=cache)
    dec = report["decode"]
    assert dec["hits"] > 0 and dec["saved_bytes"] > 0
    assert dec["hit_rate"] == pytest.approx(
        dec["hits"] / (dec["hits"] + dec["misses"])
    )
    assert report["result"]["misses"] == 1
    m = MetricsRegistry()
    collect_cache_metrics(m, store=st, result_cache=cache)
    assert m.gauge("cache_hits", cache="decode") == dec["hits"]
    assert m.gauge("cache_misses", cache="result") == 1


def test_decode_cache_byte_weighted_stats():
    st = make_nanoaod_like(4_000, n_hlt=4, basket_events=1024)
    st.read_flat("MET_pt")
    s0 = st.decode_cache_stats()
    assert s0["miss_bytes"] > 0 and s0["hit_bytes"] == 0
    st.read_flat("MET_pt")
    s1 = st.decode_cache_stats()
    assert s1["hit_bytes"] > 0
    assert s1["saved_decode_bytes"] == s1["hit_bytes"]
    assert s1["miss_bytes"] == s0["miss_bytes"]  # nothing re-decoded


def test_result_cache_replacement_not_double_counted():
    cache = SkimResultCache()
    assert cache.put("k", "v1", nbytes=100, fetch_bytes=10)
    # the timed-out-primary race: same content address re-put
    assert cache.put("k", "v1", nbytes=100, fetch_bytes=10)
    s = cache.stats
    assert s.insertions == 1
    assert s.replacements == 1
    assert s.miss_bytes == 100  # counted once, not twice
    assert s.stored_bytes == 100
    assert len(cache) == 1


# ---------------------------------------------------------------------------
# the extras lint checker
# ---------------------------------------------------------------------------


def _load_checker():
    spec = importlib.util.spec_from_file_location(
        "check_extras", ROOT / "tools" / "check_extras.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_extras_repo_is_clean():
    checker = _load_checker()
    assert checker.scan([ROOT / "src" / "repro"]) == []


def test_check_extras_flags_bare_writes(tmp_path):
    checker = _load_checker()
    bad = tmp_path / "bad.py"
    bad.write_text(
        'extras["new_key"] = 1\n'
        'extras["n"] += 2\n'
        'ok = extras["read"]\n'          # reads are fine
        '# extras["comment"] = 3\n'      # comments are fine
        'if extras["x"] == 1: pass\n'    # comparisons are fine
    )
    hits = checker.scan([bad])
    assert [h[1] for h in hits] == [1, 2]
