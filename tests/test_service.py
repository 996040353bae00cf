"""Async skim job service (ISSUE 6 / DESIGN.md §12).

Pins the tentpole contracts on the deterministic harness — injectable
:class:`ManualClock` + single-threaded :class:`DeterministicExecutor`,
no wall-clock sleeps anywhere:

  * lifecycle: submit → PENDING → RUNNING → streamed partials → DONE,
    every transition stamped by the injected clock;
  * streaming: the union of a completed job's window-granular partials
    is bit-identical to the synchronous ``run_skim`` result, each window
    streamed exactly once;
  * scheduling: per-tenant FIFO, weighted-fair across tenants (cheap
    queries are never head-of-line blocked by expensive ones), replays
    identically;
  * admission: over-quota submissions are REJECTED with the plan-priced
    estimate attached and provably zero bytes fetched;
  * cancellation: cooperative at window boundaries, streamed partials
    kept, batch members cancel without aborting the shared pass;
  * batching: coalesced shared-scan jobs finish bit-identical to solo
    runs and to ``SkimEngine.run_batch``;
  * faults: a cluster node failure FAILs the job with a cause and the
    queue keeps draining.
"""

import numpy as np
import pytest

from repro.cluster import build_cluster
from repro.core.engine import SkimEngine, run_skim
from repro.data.synth import make_nanoaod_like
from repro.serve import (
    CANCELLED,
    DONE,
    FAILED,
    PENDING,
    REJECTED,
    RUNNING,
    ClusterBackend,
    ManualClock,
    SkimService,
    TenantQuota,
    union_columns,
)
from tests.test_query import QUERY

N_EVENTS = 10_000
BASKET = 2048
N_WINDOWS = 5  # ceil(N_EVENTS / BASKET)

#: a second tenant's (compatible) query: same shape, tighter MET cut
QUERY_B = {
    **QUERY,
    "selection": {
        **QUERY["selection"],
        "event": [
            {"type": "any", "branches": ["HLT_IsoMu24"]},
            {"type": "cut", "branch": "MET_pt", "op": ">", "value": 35.0},
        ],
    },
}


@pytest.fixture(scope="module")
def store():
    return make_nanoaod_like(
        N_EVENTS, n_hlt=16, n_filler=8, basket_events=BASKET
    )


@pytest.fixture(scope="module")
def ref(store):
    return run_skim(store, QUERY, mode="near_data")


@pytest.fixture(scope="module")
def ref_b(store):
    return run_skim(store, QUERY_B, mode="near_data")


def _assert_union_matches(job, ref):
    """The streaming contract: branch-wise union of streamed partials
    equals the synchronous output bit-for-bit."""
    cols, jagged = union_columns(job)
    assert job.n_passed == ref.n_passed
    for name in ref.output.branch_names():
        br = ref.output.branches[name]
        if br.jagged:
            v0, _ = ref.output.read_jagged(name)
            np.testing.assert_array_equal(cols[name], v0)
        else:
            np.testing.assert_array_equal(
                cols[name], ref.output.read_flat(name)
            )


def _assert_result_matches(res, ref):
    assert res.n_passed == ref.n_passed
    assert res.output.compressed_bytes() == ref.output.compressed_bytes()
    for name in ref.output.branch_names():
        br = ref.output.branches[name]
        if br.jagged:
            v0, c0 = ref.output.read_jagged(name)
            v1, c1 = res.output.read_jagged(name)
            np.testing.assert_array_equal(c1, c0)
            np.testing.assert_array_equal(v1, v0)
        else:
            np.testing.assert_array_equal(
                res.output.read_flat(name), ref.output.read_flat(name)
            )


# ---------------------------------------------------------------------------
# lifecycle + streaming
# ---------------------------------------------------------------------------


def test_lifecycle_and_clock(store, ref):
    clock = ManualClock()
    svc = SkimService(store, clock=clock)
    clock.advance(5.0)
    job = svc.submit(QUERY, tenant="alice")
    assert job.state == PENDING
    assert job.submitted_at == 5.0
    assert job.started_at is None
    assert job.estimate is not None and job.estimate.est_bytes > 0

    clock.advance(1.0)
    assert svc.step()  # first quantum starts the job
    assert job.state == RUNNING
    assert job.started_at == 6.0

    clock.advance(2.0)
    svc.run_until_idle()
    assert job.state == DONE
    assert job.finished_at == 8.0
    assert job.result is not None
    _assert_result_matches(job.result, ref)


def test_streamed_union_bit_identical(store, ref):
    svc = SkimService(store)
    job = svc.submit(QUERY)
    parts = list(svc.stream(job.job_id))
    assert job.state == DONE
    assert len(parts) == N_WINDOWS
    _assert_union_matches(job, ref)
    # the job's ledger is the engine's, exposed per job
    assert job.stats.bytes_fetched == ref.stats.bytes_fetched
    assert job.stats.requests == ref.stats.requests


def test_each_window_streamed_exactly_once(store):
    svc = SkimService(store)
    job = svc.submit(QUERY)
    svc.result(job.job_id)
    spans = job.windows_streamed()
    assert spans == sorted(spans)
    assert len(spans) == len(set(spans)) == N_WINDOWS
    # gapless cover of the event range
    assert spans[0][0] == 0 and spans[-1][1] == store.n_events
    for (_, stop), (start, _) in zip(spans, spans[1:]):
        assert start == stop


# ---------------------------------------------------------------------------
# scheduling: FIFO, weighted fairness, deterministic replay
# ---------------------------------------------------------------------------


def test_same_tenant_fifo(store):
    svc = SkimService(store)
    j1 = svc.submit(QUERY, "t")
    j2 = svc.submit(QUERY, "t")
    assert j1.vfinish < j2.vfinish  # backlog continues, never overtakes
    svc.run_until_idle()
    order = [picked for _, picked, _ in svc.trace]
    assert order.index(j2.job_id) > max(
        i for i, p in enumerate(order) if p == j1.job_id
    )


def test_cheap_query_not_head_of_line_blocked(store):
    """A cheap query submitted AFTER two expensive ones must run to
    completion before the second expensive one ever starts."""
    cheap = {
        "input": "in.skim",
        "output": "out.skim",
        "branches": ["nMuon"],
        "selection": {
            "preselection": [{"branch": "nMuon", "op": ">=", "value": 100}]
        },
    }
    svc = SkimService(store)
    big1 = svc.submit(QUERY, "heavy")
    big2 = svc.submit(QUERY, "heavy")
    small = svc.submit(cheap, "light")
    assert small.vfinish < big2.vfinish
    svc.run_until_idle()
    order = [picked for _, picked, _ in svc.trace]
    assert order.index(small.job_id) < order.index(big2.job_id)
    assert all(j.state == DONE for j in (big1, big2, small))


def test_weight_scales_fair_share(store):
    """Same backlog, but the weighted tenant's virtual finish shrinks
    by its weight — a weight-4 tenant schedules 4x earlier."""
    sv_flat = SkimService(store)
    sv_wtd = SkimService(store, quotas={"t": TenantQuota(weight=4.0)})
    j_flat = sv_flat.submit(QUERY, "t")
    j_wtd = sv_wtd.submit(QUERY, "t")
    assert j_wtd.vfinish == pytest.approx(j_flat.vfinish / 4.0)


def test_deterministic_replay(store):
    def run_once():
        svc = SkimService(store)
        svc.submit(QUERY, "a")
        svc.submit(QUERY_B, "b")
        svc.submit(QUERY, "a")
        svc.run_until_idle()
        return svc.trace

    assert run_once() == run_once()


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_over_quota_rejected_without_fetching(store):
    fetches = []
    orig = store.fetch_window

    def spy(*args, **kwargs):
        fetches.append(args)
        return orig(*args, **kwargs)

    store.fetch_window = spy
    try:
        svc = SkimService(
            store, quotas={"bob": TenantQuota(byte_budget=10.0)}
        )
        job = svc.submit(QUERY, tenant="bob")
    finally:
        store.fetch_window = orig
    assert job.state == REJECTED
    assert fetches == []  # pricing is metadata-only
    assert job.stats.bytes_fetched == 0 and job.stats.requests == 0
    # the priced estimate is attached and explains the rejection
    assert job.estimate is not None
    assert "over byte quota" in job.error
    assert f"priced {job.estimate.est_bytes}" in job.error
    # rejected jobs never enter the queue
    assert svc.queue_depth() == 0 and not svc.step()


def test_wall_clock_quota(store):
    svc = SkimService(store, quotas={"t": TenantQuota(wall_budget_s=1e-9)})
    job = svc.submit(QUERY, "t")
    assert job.state == REJECTED and "over wall-clock quota" in job.error


def test_done_jobs_charge_observed_bytes(store, ref):
    # budget fits one run's estimate but not two runs' observed spend
    budget = ref.stats.bytes_fetched * 1.2
    svc = SkimService(store, quotas={"t": TenantQuota(byte_budget=budget)})
    j1 = svc.submit(QUERY, "t")
    assert j1.state == PENDING
    svc.run_until_idle()
    assert j1.state == DONE
    usage = svc.tenant_usage("t")
    assert usage["spent_bytes"] == ref.stats.bytes_fetched
    assert usage["reserved_bytes"] == 0  # reservation released on settle
    j2 = svc.submit(QUERY, "t")  # spent + new estimate now exceeds budget
    assert j2.state == REJECTED


def test_malformed_query_rejected_at_the_door(store):
    svc = SkimService(store)
    job = svc.submit(
        {
            "branches": ["event"],
            "selection": {
                "preselection": [
                    {"branch": "NoSuchBranch", "op": ">", "value": 1}
                ]
            },
        }
    )
    assert job.state == REJECTED
    assert "unpriceable query" in job.error


# ---------------------------------------------------------------------------
# cancellation
# ---------------------------------------------------------------------------


def test_cancel_pending_job(store):
    svc = SkimService(store)
    j1 = svc.submit(QUERY, "a")
    j2 = svc.submit(QUERY, "b")
    assert svc.cancel(j2.job_id)
    assert j2.state == CANCELLED and j2.partials == []
    svc.run_until_idle()
    assert j1.state == DONE
    assert not svc.cancel(j2.job_id)  # already terminal


def test_cancel_mid_stream_keeps_partials(store):
    svc = SkimService(store)
    job = svc.submit(QUERY)
    stream = svc.stream(job.job_id)
    got = [next(stream), next(stream)]
    svc.cancel(job.job_id)
    assert list(stream) == []  # stream ends at the window boundary
    assert job.state == CANCELLED
    assert job.partials == got and len(got) == 2
    assert job.result is None
    # the service is idle again: nothing left to run
    assert not svc.step()


# ---------------------------------------------------------------------------
# batching mode
# ---------------------------------------------------------------------------


def test_batch_coalesced_bit_identical(store, ref, ref_b):
    svc = SkimService(store, batching=True)
    j1 = svc.submit(QUERY, "a")
    j2 = svc.submit(QUERY_B, "b")
    svc.run_until_idle()
    assert j1.state == DONE and j2.state == DONE
    # one coalesced run unit served both jobs: every quantum lists both
    assert all(members == (1, 2) for _, _, members in svc.trace)
    _assert_result_matches(j1.result, ref)
    _assert_result_matches(j2.result, ref_b)
    _assert_union_matches(j1, ref)
    _assert_union_matches(j2, ref_b)
    # and matches the synchronous shared-scan batch exactly
    batch = SkimEngine(store).run_batch([QUERY, QUERY_B])
    _assert_result_matches(batch.results[0], ref)
    _assert_result_matches(batch.results[1], ref_b)


def test_batch_member_cancel_keeps_shared_pass(store, ref):
    svc = SkimService(store, batching=True)
    j1 = svc.submit(QUERY, "a")
    j2 = svc.submit(QUERY_B, "b")
    svc.step()  # starts the coalesced pass, streams window 0 to both
    assert j1.state == RUNNING and j2.state == RUNNING
    svc.cancel(j2.job_id)
    svc.run_until_idle()
    assert j2.state == CANCELLED and len(j2.partials) == 1
    # the surviving member finished bit-identically on the shared pass
    assert j1.state == DONE
    _assert_result_matches(j1.result, ref)
    _assert_union_matches(j1, ref)


# ---------------------------------------------------------------------------
# cluster backend: streaming, bit-identity, failure injection
# ---------------------------------------------------------------------------


def test_cluster_backend_bit_identical(store, ref):
    coord = build_cluster(store, 3)
    svc = SkimService(ClusterBackend(coord))
    job = svc.submit(QUERY)
    assert job.estimate.est_bytes > 0  # priced across all shards
    svc.run_until_idle()
    assert job.state == DONE
    # one shard-granular partial per shard, in shard order
    assert [p.meta["window"] for p in job.partials] == [0, 1, 2]
    assert sum(p.n_passed for p in job.partials) == ref.n_passed
    _assert_result_matches(job.result, ref)


def test_cluster_node_fault_fails_job_queue_drains(store, ref):
    coord = build_cluster(store, 3, replication=False)
    coord.nodes[1].inject_fault("fail")  # one-shot: only the first job hits it
    svc = SkimService(ClusterBackend(coord))
    j1 = svc.submit(QUERY, "a")
    j2 = svc.submit(QUERY, "b")
    svc.run_until_idle()
    assert j1.state == FAILED
    assert "shard 1" in j1.error and "no replica" in j1.error
    assert j1.result is None
    # the queue kept draining past the failure
    assert j2.state == DONE
    _assert_result_matches(j2.result, ref)
