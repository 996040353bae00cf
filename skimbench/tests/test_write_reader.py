"""The reader of ``write.clone_share`` on hand-built span events: the
share computed by hand from the ``write`` spans that open inside the
window, and ``None`` where there are none."""

from types import SimpleNamespace

import pytest

from harness import spec


def _ev(cat, ts_s, dur_s, **args):
    return {"cat": cat, "ph": "X", "ts": ts_s * 1e6, "dur": dur_s * 1e6, "args": args}


def _run(spans):
    # window [10 s, 20 s); spans opening before it or at its end stay out
    return SimpleNamespace(t0=10.0, t_cut=20.0, events_in_window=2_000_000, spans=spans)


SPANS = [
    _ev("write", 9.0, 2.0, baskets_cloned=500, baskets_encoded=0),
    _ev("write", 12.0, 0.4, baskets_cloned=179, baskets_encoded=0),
    _ev("write", 15.0, 0.5, baskets_cloned=40, baskets_encoded=139, tenant=0),
    _ev("write", 18.0, 0.1, baskets_cloned=0, baskets_encoded=0),
    _ev("write", 20.0, 0.3, baskets_cloned=0, baskets_encoded=900),
    _ev("phase2", 13.0, 1.0, bytes=10),
]


def test_reader_matches_hand_count():
    reader = spec._reader("write.clone_share")
    assert reader.read(_run(SPANS)) == pytest.approx((179 + 40) / (179 + 40 + 139), rel=1e-12)


def test_reader_is_silent_without_write_spans():
    reader = spec._reader("write.clone_share")
    assert reader.read(_run([e for e in SPANS if e["cat"] != "write"])) is None
    assert reader.read(_run([])) is None
    # write spans without the counts, as a program before cloning records them
    assert reader.read(_run([_ev("write", 12.0, 4.0)])) is None


def test_reader_is_declared_for_its_cells():
    import json
    import os

    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    declared = {m["name"]: m for m in bench["per_layer"]}["write.clone_share"]
    assert declared["moves"] == "events_per_s"
    assert declared["layer"] == "write"
    assert declared["workloads"] == ["skim_node.higgs", "skim_node.slim", "adl2012b.q5_dimuon"]
