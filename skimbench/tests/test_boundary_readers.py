"""The readers of the host–device boundary metrics, on hand-built span
events: each returns the value computed by hand from the spans that open
inside the window, and ``None`` where its spans are absent."""

from types import SimpleNamespace

import pytest

from harness import spec


def _ev(cat, ts_s, dur_s, **args):
    return {"cat": cat, "ph": "X", "ts": ts_s * 1e6, "dur": dur_s * 1e6, "args": args}


def _run(spans, events=2_000_000):
    # window [10 s, 20 s); spans opening before or at its end stay out
    return SimpleNamespace(t0=10.0, t_cut=20.0, events_in_window=events, spans=spans)


SPANS = [
    _ev("device_wait", 9.5, 0.2, op="basket_decode", d2h_bytes=1_000, arrays=1),
    _ev("device_wait", 10.0, 0.5, op="basket_decode", d2h_bytes=4_000, arrays=1),
    _ev("device_wait", 12.0, 1.5, op="fused_skim", d2h_bytes=6_000, arrays=2),
    _ev("device_wait", 19.0, 0.25, op="cascade_stage", d2h_bytes=2_000, arrays=2),
    _ev("device_wait", 20.0, 0.1, op="basket_decode", d2h_bytes=9_999, arrays=1),
    _ev("device_launch", 11.0, 0.1, op="basket_decode", h2d_bytes=3_000_000),
    _ev("device_launch", 15.0, 0.1, op="fused_skim", h2d_bytes=1_000_000),
    _ev("device_launch", 25.0, 0.1, op="fused_skim", h2d_bytes=7),
    _ev("stage_inputs", 14.0, 0.75, events=4096, K=8),
    _ev("stage_inputs", 16.0, 0.5, events=4096, K=8),
    _ev("stage_inputs", 21.0, 9.0, events=4096, K=8),
    _ev("decode_device", 10.0, 5.0),
]

# by hand, over [10, 20) and 2M events
EXPECTED = {
    "device.syncs_per_mevent": 3 / 2,
    "device.host_wait_frac": (0.5 + 1.5 + 0.25) / 10,
    "transfer.h2d_bytes_per_event": 4_000_000 / 2_000_000,
    "transfer.d2h_bytes_per_event": 12_000 / 2_000_000,
    "cascade.staging_s_per_mevent": (0.75 + 0.5) / 2,
}
SPAN_KIND = {
    "device.syncs_per_mevent": "device_wait",
    "device.host_wait_frac": "device_wait",
    "transfer.h2d_bytes_per_event": "device_launch",
    "transfer.d2h_bytes_per_event": "device_wait",
    "cascade.staging_s_per_mevent": "stage_inputs",
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_matches_hand_count(name):
    assert spec._reader(name).read(_run(SPANS)) == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_is_silent_without_its_spans(name):
    reader = spec._reader(name)
    others = [e for e in SPANS if e["cat"] != SPAN_KIND[name]]
    assert reader.read(_run(others)) is None
    assert reader.read(_run([])) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_is_silent_without_events(name):
    if name == "device.host_wait_frac":  # a share of the window, not per event
        assert spec._reader(name).read(_run(SPANS, events=0)) == pytest.approx(0.225)
    else:
        assert spec._reader(name).read(_run(SPANS, events=0)) is None


def test_every_reader_is_declared_for_its_cells():
    import json
    import os

    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        assert declared[name]["moves"] == "events_per_s"
    assert declared["cascade.staging_s_per_mevent"]["workloads"] == ["skim_node.higgs"]
