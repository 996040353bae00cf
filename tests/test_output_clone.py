"""Output basket cloning (DESIGN.md §18): a skim's output store equals
``EventStore.from_arrays`` on its concatenated survivors, field for
field, whether its baskets were cloned from the input or encoded.  An
output basket is cloned only when it holds exactly one wholly kept input
basket, aligned to ``basket_events`` in the output, and its blob still
matches its digest; the ``write`` span counts both kinds."""

import numpy as np
import pytest

from repro.core.engine import SkimEngine
from repro.data.codecs import bitpack_raw_parts
from repro.data.store import EventStore
from repro.obs.trace import Tracer
from repro.serve.engine import SharedScanEngine

B = 64  # basket_events of every store here


def _columns(n: int, keep: np.ndarray, seed: int = 7):
    rng = np.random.default_rng(seed)
    counts = rng.poisson(1.5, n).astype(np.int32)
    total = int(counts.sum())
    columns = {
        "event": np.arange(n, dtype=np.int32),
        "run": np.full(n, 362_104, dtype=np.int32),
        "keep": keep,
        "HLT_x": rng.random(n) < 0.3,
        # quarter-GeV steps in [32, 64): one exponent, so bitpack packs them
        "MET_pt": (32.0 + 0.25 * rng.integers(0, 128, n)).astype(np.float32),
        # white noise: bitpack stores raw float literals
        "Filler": rng.normal(0.0, 1.0, n).astype(np.float32),
        "nMuon": counts,
        "Muon_pt": (rng.exponential(25.0, total) + 3.0).astype(np.float32),
        "Muon_charge": rng.choice(np.array([-1, 1], dtype=np.int32), total),
    }
    jagged = {"Muon_pt": "nMuon", "Muon_charge": "nMuon"}
    return columns, jagged


BRANCHES = ["event", "run", "keep", "HLT_x", "MET_pt", "Filler", "Muon_*"]
KEEP_QUERY = {
    "branches": BRANCHES,
    "selection": {"event": [{"type": "any", "branches": ["keep"]}]},
}
SELECTIVE_QUERY = {
    "branches": BRANCHES,
    "selection": {
        "preselection": [{"branch": "Filler", "op": ">", "value": 0.0}]
    },
}
N_BRANCHES = 9  # the outputs above, with nMuon


def _span_keep(n, lo, hi):
    keep = np.ones(n, dtype=bool)
    keep[lo:hi] = False
    return keep


# case -> (n_events, chunk_events, keep mask, cloned output baskets per branch)
CASES = {
    "every_event_kept": (8 * B, B, np.ones(8 * B, bool), 8),
    "pruned_then_accepted": (8 * B, B, np.arange(8 * B) >= 3 * B, 5),
    # window 2 keeps 20 events: every later basket is off its alignment
    "partly_kept_misaligns": (8 * B, B, _span_keep(8 * B, 2 * B + 10, 3 * B - 10), 2),
    # windows 2 and 3 keep B - 10 and 10 events: window 4 lands aligned again
    "partly_kept_realigns": (8 * B, B, _span_keep(8 * B, 3 * B - 10, 4 * B - 10), 6),
    "short_last_basket": (7 * B + 23, B, np.arange(7 * B + 23) >= B, 7),
    # window 0 (two baskets) is kept in part: its kept basket is encoded
    "chunk_twice_basket": (8 * B, 2 * B, np.arange(8 * B) >= B, 6),
    # a basket spans two wholly kept windows
    "chunk_one_and_a_half_baskets": (8 * B, 3 * B // 2, np.ones(8 * B, bool), 8),
    # basket 1 starts in wholly kept window 0 but ends in partly kept window 1
    "basket_leaves_kept_window": (
        8 * B, 3 * B // 2, _span_keep(8 * B, 3 * B // 2 + 4, 3 * B // 2 + 14), 1
    ),
    "empty_output": (8 * B, B, np.zeros(8 * B, bool), 0),
    "selective": (8 * B, B, np.random.default_rng(3).random(8 * B) < 0.5, 0),
}


def _store(n, keep):
    columns, jagged = _columns(n, keep)
    return EventStore.from_arrays(columns, jagged=jagged, basket_events=B)


def _drive(gen):
    """Partials yielded by an executor generator, and its result."""
    partials = []
    while True:
        try:
            partials.append(next(gen))
        except StopIteration as stop:
            return partials, stop.value


def _reference(store, names, partials):
    """``from_arrays`` on the concatenated survivors of the streamed
    partials: the output path before cloning."""
    kept = [p for p in partials if p.n_passed]
    jagged: dict = {}
    for p in kept:
        jagged.update(p.jagged)
    if kept:
        cols = {n: np.concatenate([p.cols[n] for p in kept]) for n in names}
    else:
        cols = {n: np.empty(0, dtype=store.branches[n].np_dtype()) for n in names}
    return EventStore.from_arrays(
        cols, jagged=jagged, basket_events=store.basket_events, codec=store.codec
    )


def _assert_same_store(out, ref):
    assert list(out.branches) == list(ref.branches)
    assert out.branches == ref.branches
    assert out.n_events == ref.n_events
    assert (out.basket_events, out.codec) == (ref.basket_events, ref.codec)
    assert out._baskets == ref._baskets
    assert out._blobs == ref._blobs
    assert out.manifest_hash() == ref.manifest_hash()
    assert out.compressed_bytes() == ref.compressed_bytes()


def _write_counts(tracer, tenant=None):
    (span,) = [
        s for s in tracer.spans()
        if s.kind == "write" and s.attrs.get("tenant") == tenant
    ]
    return span.attrs["baskets_cloned"], span.attrs["baskets_encoded"]


def _run_one(store, chunk, query, executor, tracer):
    """``[(result, partials, tenant)]`` of ``query`` on one executor; the
    shared scan runs it beside the selective tenant."""
    if executor == "skim_engine":
        engine = SkimEngine(store, chunk_events=chunk, tracer=tracer)
        partials, result = _drive(engine.iter_run(query))
        return [(result, partials, None)]
    engine = SharedScanEngine(store, chunk_events=chunk)
    batches, batch = _drive(engine.iter_batch([query, SELECTIVE_QUERY], tracer=tracer))
    return [
        (res, [b.tenants[i] for b in batches], i)
        for i, res in enumerate(batch.results)
    ]


@pytest.mark.parametrize("executor", ["skim_engine", "shared_scan"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_output_equals_from_arrays_reference(case, executor):
    n, chunk, keep, cloned_per_branch = CASES[case]
    store = _store(n, keep)
    tracer = Tracer()
    runs = _run_one(store, chunk, KEEP_QUERY, executor, tracer)
    for result, partials, tenant in runs:
        ref = _reference(store, result.plan.output_branches, partials)
        _assert_same_store(result.output, ref)
        cloned, encoded = _write_counts(tracer, tenant)
        n_baskets = sum(ref.n_baskets(name) for name in ref.branches)
        assert cloned + encoded == n_baskets
        if tenant == 1:  # the selective tenant beside the case's query
            assert cloned == 0
    result, _, tenant = runs[0]
    np.testing.assert_array_equal(
        result.output.read_flat("event"), np.arange(n, dtype=np.int32)[keep]
    )
    cloned, encoded = _write_counts(tracer, tenant)
    assert cloned == cloned_per_branch * N_BRANCHES
    if case == "every_event_kept":
        assert encoded == 0


def test_cases_cover_float_literals_and_packed_floats():
    store = _store(8 * B, np.ones(8 * B, bool))
    kinds = {
        name: {bitpack_raw_parts(blob)["kind"] for blob in store._blobs[name]}
        for name in ("MET_pt", "Filler")
    }
    assert 3 not in kinds["MET_pt"]  # packed planes
    assert kinds["Filler"] == {3}  # raw literals


@pytest.mark.parametrize("executor", ["skim_engine", "shared_scan"])
def test_corrupted_blob_is_encoded_not_cloned(executor):
    """A blob corrupted after its window was read — phase 2 served, the
    decode LRU warm — fails the write's digest check: that one basket is
    encoded from the survivors, and the output still equals the
    reference."""
    n = 8 * B
    store = _store(n, np.ones(n, bool))
    store.decode_cache_baskets = 1024
    _run_one(store, B, KEEP_QUERY, executor, Tracer())  # warm the decode LRU
    tracer = Tracer()
    if executor == "skim_engine":
        gen = SkimEngine(store, chunk_events=B, tracer=tracer).iter_run(KEEP_QUERY)
    else:
        gen = SharedScanEngine(store, chunk_events=B).iter_batch(
            [KEEP_QUERY], tracer=tracer
        )
    first = next(gen)  # window 0 is read, kept and streamed
    good = store._blobs["MET_pt"][0]
    restore = store.corrupt_blob("MET_pt", 0)
    rest, final = _drive(gen)
    restore()
    if executor == "skim_engine":
        result, partials, tenant = final, [first, *rest], None
    else:
        result, tenant = final.results[0], 0
        partials = [b.tenants[0] for b in [first, *rest]]
    ref = _reference(store, result.plan.output_branches, partials)
    _assert_same_store(result.output, ref)
    assert result.output._blobs["MET_pt"][0] == good
    assert _write_counts(tracer, tenant) == (8 * N_BRANCHES - 1, 1)
