"""Spans at the host–device boundary (DESIGN.md §13a).

Pins the contracts of the ``decode_prep`` / ``stage_inputs`` /
``device_launch`` / ``device_wait`` spans on the CPU (the device decode's
jitted mirror and the fused oracle stand in for the Pallas kernels):

  * every decode round that reaches the device is one launch per group
    of like baskets and one wait under its ``decode_device`` span,
    counted against the rounds that reached ``ops.basket_decode_batch``,
    with bytes that cover what crossed;
  * the cascade's device stages — per window and window-batched — record
    staging, launch and wait under their ``cascade_stage`` span;
  * ``fetch`` means one store read: ``load_window``/``phase2`` are kinds
    of their own;
  * the export stays byte-identical under a ManualClock and the no-op
    tracer changes no result;
  * each span opened live is mirrored into the JAX profiler's host trace
    at one constant offset from the span's own clock.
"""

import collections
import glob
import os

import numpy as np
import pytest

from repro.core.engine import SkimEngine
from repro.data import codecs
from repro.data.store import EventStore
from repro.data.synth import make_nanoaod_like
from repro.kernels import ops
from repro.obs import NULL_TRACER, Tracer, chrome_trace, trace_json
from repro.serve import ManualClock, SkimService
from repro.serve.engine import SharedScanEngine
from repro.serve.service import EngineBackend

N_EVENTS = 6_000
BASKET = 2048
HEADER_BYTES = 4 * codecs._HEADER_WORDS

QUERY = {
    "branches": ["Electron_*", "Jet_*", "MET_*", "run", "event"],
    "selection": {
        "preselection": [{"branch": "nElectron", "op": ">=", "value": 1}],
        "object": [
            {
                "collection": "Electron",
                "cuts": [{"var": "pt", "op": ">", "value": 40.0}],
                "min_count": 1,
            }
        ],
        "event": [
            {"type": "any", "branches": ["HLT_IsoMu24", "HLT_Ele32_WPTight_Gsf"]},
            {"type": "cut", "branch": "MET_pt", "op": ">", "value": 44.0},
        ],
    },
}


def _binade_store(seed: int = 5) -> EventStore:
    """Every float in [32, 64): one exponent, so no basket bails out to
    raw literals and every decode crosses to the device tier."""
    rng = np.random.default_rng(seed)
    cols, jagged = {}, {}
    for coll, mean in (("Electron", 1.2), ("Jet", 3.0)):
        counts = rng.poisson(mean, N_EVENTS).astype(np.int32)
        cols[f"n{coll}"] = counts
        for var in ("pt", "eta"):
            cols[f"{coll}_{var}"] = (32.0 + 32.0 * rng.random(counts.sum())).astype(np.float32)
            jagged[f"{coll}_{var}"] = f"n{coll}"
    cols["MET_pt"] = (32.0 + 32.0 * rng.random(N_EVENTS)).astype(np.float32)
    cols["run"] = np.full(N_EVENTS, 362_104, dtype=np.int32)
    cols["event"] = np.arange(N_EVENTS, dtype=np.int32)
    cols["HLT_IsoMu24"] = rng.random(N_EVENTS) < 0.3
    cols["HLT_Ele32_WPTight_Gsf"] = rng.random(N_EVENTS) < 0.3
    return EventStore.from_arrays(
        cols, jagged=jagged, basket_events=BASKET, decode_backend="device"
    )


def _children(tr) -> dict:
    kids = collections.defaultdict(list)
    for s in tr.spans():
        if s.parent is not None:
            kids[s.parent].append(s)
    return kids


def _of(tr, kind: str) -> list:
    return [s for s in tr.spans() if s.kind == kind]


@pytest.fixture()
def counted(monkeypatch):
    """Counts the decode rounds that reach the device in
    ``ops.basket_decode_batch`` and the baskets that cross in them (raw
    literal and empty baskets stay on the host), and the compressed bytes
    of every basket the store decodes on a cache miss."""
    seen = {"device_rounds": 0, "device_baskets": 0, "plane_bytes": 0}
    orig_batch = ops.basket_decode_batch
    orig_decode = EventStore._decode_batch

    def batch(parts_list, *a, **kw):
        crossing = sum(
            p["n"] > 0 and p["kind"] != codecs.KIND_RAW_F32 for p in parts_list
        )
        seen["device_rounds"] += crossing > 0
        seen["device_baskets"] += crossing
        return orig_batch(parts_list, *a, **kw)

    def decode(self, blobs, *a, **kw):
        seen["plane_bytes"] += sum(len(b) - HEADER_BYTES for b in blobs)
        return orig_decode(self, blobs, *a, **kw)

    monkeypatch.setattr(ops, "basket_decode_batch", batch)
    monkeypatch.setattr(EventStore, "_decode_batch", decode)
    return seen


def test_every_device_decode_is_one_launch_and_one_wait(counted):
    """One fetch round decodes in one device round trip: a launch per
    group of like baskets, then a single wait reading all of them."""
    st = _binade_store()
    tr = Tracer(clock=ManualClock())
    res = SkimEngine(st, chunk_events=BASKET).run(QUERY, tracer=tr)
    assert res.n_passed > 0
    kids = _children(tr)
    decodes = _of(tr, "decode_device")
    assert decodes
    for d in decodes:
        kinds = collections.Counter(c.kind for c in kids[d.sid])
        if kinds:  # a span whose baskets all hit the decode cache has none
            assert kinds["decode_prep"] >= 1
            assert kinds["device_wait"] == 1
            assert kinds["device_launch"] >= 1
            [wait] = [c for c in kids[d.sid] if c.kind == "device_wait"]
            assert wait.attrs["arrays"] == kinds["device_launch"]
    waits = [s for s in _of(tr, "device_wait") if s.attrs["op"] == "basket_decode"]
    assert all(tr.get(s.parent).kind == "decode_device" for s in waits)
    assert len(waits) == counted["device_rounds"] > 0
    launches = [s for s in _of(tr, "device_launch") if s.attrs["op"] == "basket_decode"]
    assert all(s.attrs["baskets"] >= 1 for s in launches)
    assert sum(s.attrs["baskets"] for s in launches) == counted["device_baskets"]
    # grouping engages: fewer launches than baskets decoded on the device
    assert len(launches) < counted["device_baskets"]
    assert sum(bool(kids[d.sid]) for d in decodes) > len(decodes) // 2


def test_decode_bytes_cover_what_crossed(counted):
    st = _binade_store()
    tr = Tracer()
    SkimEngine(st, chunk_events=BASKET).run(QUERY, tracer=tr)
    d2h = sum(
        s.attrs["d2h_bytes"] for s in _of(tr, "device_wait")
        if s.attrs["op"] == "basket_decode"
    )
    h2d = sum(
        s.attrs["h2d_bytes"] for s in _of(tr, "device_launch")
        if s.attrs["op"] == "basket_decode"
    )
    miss_bytes = st.decode_cache_stats()["miss_bytes"]
    assert miss_bytes > 0
    assert d2h >= miss_bytes
    assert h2d >= counted["plane_bytes"] > 0


@pytest.mark.parametrize(
    "engine_kw", [{"fused_backend": "xla"}, {"device_batch": 4}],
    ids=["per_window", "batched"],
)
def test_cascade_stages_stage_launch_and_wait(engine_kw):
    st = make_nanoaod_like(n_events=N_EVENTS, basket_events=BASKET, seed=7)
    tr = Tracer(clock=ManualClock())
    eng = SkimEngine(st, chunk_events=BASKET, **engine_kw)
    res = eng.run(QUERY, tracer=tr)
    ref = SkimEngine(st, chunk_events=BASKET, **engine_kw).run(QUERY)
    assert res.n_passed == ref.n_passed > 0
    kids = _children(tr)
    stages = _of(tr, "cascade_stage")
    assert stages
    under = collections.Counter(c.kind for s in stages for c in kids[s.sid])
    assert under["stage_inputs"] >= 1
    assert under["device_launch"] == under["device_wait"] >= 1
    for s in stages:
        kinds = collections.Counter(c.kind for c in kids[s.sid])
        assert kinds["device_launch"] == kinds["device_wait"]
        assert kinds["device_launch"] <= kinds["stage_inputs"]
    ops_seen = {
        c.attrs["op"] for s in stages for c in kids[s.sid]
        if c.kind in ("device_launch", "device_wait")
    }
    assert ops_seen == ({"cascade_stage"} if "device_batch" in engine_kw else {"fused_skim"})
    for c in (c for s in stages for c in kids[s.sid]):
        if c.kind == "device_launch":
            assert c.attrs["h2d_bytes"] > 0
        elif c.kind == "device_wait":
            assert c.attrs["d2h_bytes"] > 0 and c.attrs["arrays"] == 2
        elif c.kind == "stage_inputs":
            assert c.attrs["events"] > 0 and c.attrs["K"] >= 1


def test_fused_path_stages_inside_its_kernel_span():
    st = make_nanoaod_like(n_events=N_EVENTS, basket_events=BASKET, seed=7)
    tr = Tracer(clock=ManualClock())
    SkimEngine(st, chunk_events=BASKET, cascade=False, fused_backend="xla").run(
        QUERY, tracer=tr
    )
    kids = _children(tr)
    kernels = _of(tr, "kernel")
    assert kernels
    for k in kernels:
        assert [c.kind for c in kids[k.sid]] == ["stage_inputs", "device_launch", "device_wait"]


@pytest.mark.parametrize("shared", [False, True], ids=["engine", "shared_scan"])
def test_fetch_is_a_store_read_only(shared):
    st = make_nanoaod_like(n_events=N_EVENTS, basket_events=BASKET, seed=7)
    tr = Tracer(clock=ManualClock())
    if shared:
        SharedScanEngine(st, chunk_events=BASKET).run_batch([QUERY, QUERY], tracer=tr)
    else:
        SkimEngine(st, chunk_events=BASKET).run(QUERY, tracer=tr)
    kids = _children(tr)
    fetches = _of(tr, "fetch")
    assert fetches
    assert all(c.kind != "fetch" for f in fetches for c in kids[f.sid])
    assert all(tr.get(f.parent).kind != "fetch" for f in fetches)
    assert _of(tr, "load_window") and _of(tr, "phase2")


def _service_drain(tracing: bool):
    svc = SkimService(
        EngineBackend(_binade_store()), clock=ManualClock(), tracing=tracing
    )
    jobs = [svc.submit(QUERY, tenant=f"t{i}") for i in range(2)]
    svc.run_until_idle()
    assert all(j.state == "DONE" for j in jobs)
    return svc, jobs


def test_boundary_export_is_byte_identical_and_null_tracer_changes_nothing():
    a, jobs_a = _service_drain(True)
    b, _ = _service_drain(True)
    doc = a.export_trace()
    assert trace_json(doc) == trace_json(b.export_trace())
    cats = {e.get("cat") for e in doc["traceEvents"]}
    assert {"decode_prep", "device_launch", "device_wait"} <= cats
    off, jobs_off = _service_drain(False)
    for ja, jo in zip(jobs_a, jobs_off):
        pa, po = ja.partials, jo.partials
        assert [p.n_passed for p in pa] == [p.n_passed for p in po]
        for x, y in zip(pa, po):
            assert x.cols.keys() == y.cols.keys()
            for k in x.cols:
                np.testing.assert_array_equal(x.cols[k], y.cols[k])
    assert NULL_TRACER.spans() == []


MIRRORED = (
    "query", "window", "load_window", "phase2", "cascade_stage",
    "decode_device", "decode_prep", "device_launch", "device_wait",
)


def test_live_spans_mirror_into_the_profiler_at_one_offset(tmp_path):
    import jax
    from jax.profiler import ProfileData

    st = _binade_store()
    eng = SkimEngine(st, chunk_events=BASKET)
    eng.run(QUERY)  # compile outside the trace
    st.decode_cache_baskets = 0  # and decode every basket again inside it
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(QUERY, tracer=tr)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in MIRRORED:
                    events[ev.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
    exported = {e["sid"]: e for e in (s.as_dict() for s in tr.spans())}
    offsets = []
    for kind in MIRRORED:
        spans = sorted((s["t0"], s["t1"]) for s in exported.values() if s["kind"] == kind)
        evs = sorted(events[kind])
        assert len(spans) == len(evs) > 0, kind
        offsets += [
            (a - t0 * 1e9, b - t1 * 1e9) for (t0, t1), (a, b) in zip(spans, evs)
        ]
    mid = float(np.median([o for o, _ in offsets]))
    # each annotation opens just after its span and closes just before
    # it: one of its two ends sits within 100 us of the common offset
    # even where the process was descheduled between the two readings
    worst = max(min(abs(a - mid), abs(b - mid)) for a, b in offsets)
    assert worst < 100_000, worst
    assert not _of(tr, "plan") or "plan" not in events


def test_null_tracer_and_add_span_open_no_annotation(monkeypatch):
    import jax

    opened = []

    class Probe:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Probe)
    with NULL_TRACER.span("x", kind="device_wait"):
        NULL_TRACER.end(NULL_TRACER.begin("y", kind="device_launch"))
    tr = Tracer(clock=ManualClock())
    tr.add_span("plan", kind="plan")
    assert opened == []
    with tr.span("w", kind="device_wait"):
        tr.end(tr.begin("l", kind="device_launch"))
    assert opened == ["device_wait", "device_launch"]
    assert [s.mirror for s in tr.spans()] == [None, None, None]
    assert trace_json(chrome_trace([(0, "t", tr)]))
