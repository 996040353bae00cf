"""The trace reduction, checked on a small trace recorded on a TPU v5e
(``record_trace.py skim_node.higgs``: a 20,000-event store, a 0.3 s traced window of the
skim node's service) and on hand-made intervals."""

import gzip
import json
import os
import shutil

import numpy as np
import pytest

from harness import devtrace, spec

FIX = os.path.join(spec.BENCH_DIR, "fixtures")
KERNELS = json.load(open(os.path.join(spec.BENCH_DIR, "kernels.json")))


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    meta = json.load(open(os.path.join(FIX, "small.json")))
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(os.path.join(FIX, "small.xplane.pb.gz")) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return devtrace.load(str(path), meta["annotation"], meta["t0_ns"], meta["t1_ns"])


def _sweep_union(starts, ends):
    """Busy time by an endpoint sweep (+1 at a start, -1 at an end)."""
    ev = sorted([(s, 1) for s in starts] + [(e, -1) for e in ends], key=lambda x: (x[0], -x[1]))
    depth, last, busy = 0, None, 0.0
    for t, d in ev:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_trace_has_the_chip_and_its_kernels(trace):
    assert trace.n_devices == 1 and len(trace.names) > 50
    assert 0.25 < trace.window_s < 1.0
    for family in ("predicate", "decode"):
        assert trace.kernel_s(KERNELS[family]) > 0, family
    kernels = trace.kernel_s(KERNELS["predicate"]) + trace.kernel_s(KERNELS["decode"])
    assert kernels <= trace.busy_s()


def test_busy_and_gaps_partition_the_window(trace):
    busy = trace.busy_s()
    assert busy == pytest.approx(_sweep_union(trace.starts, trace.ends) / 1e9, rel=1e-12)
    idle = sum(b - a for a, b in trace.gaps()) / 1e9
    assert busy + idle == pytest.approx(trace.window_s, rel=1e-9)
    assert 0 < busy < trace.window_s
    assert (trace.starts >= trace.t0).all() and (trace.ends <= trace.t1).all()


def test_top_ops_are_sorted_short_names(trace):
    top = trace.top_ops(10)
    assert 1 <= len(top) <= 10
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)
    assert all(" " not in k and not k.startswith("%") for k, _ in top)
    assert devtrace.short_name("%skim_fused.1 = (f32[4096,1]) custom-call(x)") == "skim_fused"


def test_union_of_overlapping_intervals():
    s = np.array([0.0, 5.0, 2.0, 20.0])
    e = np.array([3.0, 6.0, 4.0, 21.0])
    assert devtrace._union_ns(s, e) == 4.0 + 1.0 + 1.0


def test_gaps_are_labelled_by_the_innermost_open_span():
    gaps = [(10.0, 20.0), (40.0, 50.0), (90.0, 100.0)]
    spans = [  # (kind, t0 s, t1 s); offset 0 maps seconds * 1e9 to ns
        ("job", 0.0, 80e-9),
        ("window", 5e-9, 30e-9),
        ("decode", 12e-9, 18e-9),
    ]
    out = dict(devtrace.label_gaps(gaps, spans, 0.0))
    assert out == {"decode": 10e-9, "job": 10e-9, "outside_spans": 10e-9}
