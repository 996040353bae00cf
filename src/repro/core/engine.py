"""Skim execution engine — reproduces the paper's four compared systems.

Modes (Fig. 4/5 of the paper):

  * ``client_plain``    — legacy client-side filtering: every selected
    branch's baskets cross the network for every event; everything is
    decompressed and deserialized before filtering (Fig. 2b).
  * ``client_opt``      — client-side with SkimROOT's two-phase model
    ("Client Opt"): phase 1 moves only filter branches; phase 2 moves
    output-only baskets for surviving ranges.
  * ``server_side``     — two-phase filtering on the storage server
    itself: no network for input baskets, but local reads are
    per-basket/on-demand (no TTreeCache batching — paper §4), adding
    request latency and stalling the decode pipeline.
  * ``near_data``       — SkimROOT: two-phase filtering next to storage
    (DPU analogue), coalesced high-bandwidth fetches, hardware-class
    (vectorized bitplane) decode, survivor-only output over the WAN.

``near_data`` additionally runs the **pipelined fused executor** by
default (DESIGN.md §4): the coalesced fetch + decode of basket window
*i+1* overlaps filtering of window *i* (double-buffered; modeled from
exact per-window records by default, realized by the
:class:`repro.data.store.WindowPrefetcher` worker thread with
``pipeline="threads"``), and phase 1 evaluates the query as a compiled
predicate program fused with stream compaction — the Pallas VMEM kernel
``repro.kernels.skim_fused`` on TPU, the jagged-layout program
interpreter on plain CPUs.  ``fused=False`` / ``pipeline=False`` select
the reference two-pass serial path; all paths produce bit-identical
survivor sets and outputs.

Compute stages (decompress / deserialize / filter / write) are *measured*
on this host; link stages are *modeled* from accounted bytes via
:class:`NetworkModel` — the container has no real 1/10/100 Gb/s WAN, so the
byte accounting is exact and the time model is explicit (DESIGN.md §2c).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from repro.core.branchmap import with_counts_branches
from repro.core.plan import CascadeExecutor, mark_fetched, unfetched_bytes
from repro.core.planner import SkimPlan, plan_skim
from repro.core.query import Query, eval_stage, parse_query
from repro.core.zonemap import ACCEPT_ALL, PRUNE, SCAN
from repro.data.store import (
    EventStore,
    FetchStats,
    WindowPrefetcher,
    coalesced_requests,
)
from repro.obs.schema import SkimReport
from repro.obs.trace import NULL_TRACER


@dataclass
class NetworkModel:
    """Analytic link-time model: serialization + per-request round trips."""

    bandwidth_gbps: float = 1.0
    rtt_s: float = 0.001

    def transfer_time(self, nbytes: int, n_requests: int = 1) -> float:
        return nbytes * 8.0 / (self.bandwidth_gbps * 1e9) + n_requests * self.rtt_s


# Link tiers used throughout the evaluation (paper §4; DESIGN.md §2c).
WAN_1G = NetworkModel(1.0, rtt_s=0.010)
LAN_10G = NetworkModel(10.0, rtt_s=0.001)
LAN_100G = NetworkModel(100.0, rtt_s=0.0005)
PCIE_128G = NetworkModel(128.0, rtt_s=0.00002)  # DPU<->host PCIe Gen3 x16
LOCAL_DISK = NetworkModel(16.0, rtt_s=0.0005)  # on-demand local reads, seek-y


@dataclass
class Breakdown:
    """Per-operation seconds; mirrors Fig. 4b / 5a."""

    fetch: float = 0.0  # input basket movement (modeled link / disk time)
    decompress: float = 0.0  # measured
    deserialize: float = 0.0  # measured
    filter: float = 0.0  # measured
    write: float = 0.0  # measured
    output_transfer: float = 0.0  # modeled (filtered file -> client)

    def total(self) -> float:
        return (
            self.fetch
            + self.decompress
            + self.deserialize
            + self.filter
            + self.write
            + self.output_transfer
        )

    def as_dict(self) -> dict:
        return {
            "fetch": self.fetch,
            "decompress": self.decompress,
            "deserialize": self.deserialize,
            "filter": self.filter,
            "write": self.write,
            "output_transfer": self.output_transfer,
            "total": self.total(),
        }

    def merge(self, other: "Breakdown") -> None:
        """Accumulate another breakdown (per-window accounting merge)."""
        self.fetch += other.fetch
        self.decompress += other.decompress
        self.deserialize += other.deserialize
        self.filter += other.filter
        self.write += other.write
        self.output_transfer += other.output_transfer

    @classmethod
    def merged(cls, parts: "list[Breakdown]") -> "Breakdown":
        """Sum a sequence of breakdowns into a fresh object (the cluster
        coordinator's gather contract — inputs are left untouched)."""
        out = cls()
        for p in parts:
            out.merge(p)
        return out


@dataclass
class SkimResult:
    mode: str
    output: EventStore
    n_input: int
    n_passed: int
    breakdown: Breakdown
    stats: FetchStats
    plan: SkimPlan
    busy_fraction: float = 1.0  # compute_time / total -> Fig. 5b proxy
    extras: dict = field(default_factory=dict)
    # structured form of `extras` (repro.obs.schema.SkimReport); extras
    # is rendered FROM it via the compatibility shim and stays the
    # read-side contract for existing callers
    report: object = None

    @property
    def selectivity(self) -> float:
        return self.n_passed / max(self.n_input, 1)


@dataclass
class WindowPartial:
    """One basket window's completed ledger entry, streamed mid-skim.

    The executor yields one of these per window, in window order, as soon
    as that window's phase 2 finishes (DESIGN.md §12).  ``cols`` holds the
    window's survivor columns exactly as they will be concatenated into
    the final output — so the union of a run's partials is bit-identical
    to the synchronous result by construction.  ``n_passed == 0`` windows
    still stream (empty ``cols``): the ledger entry is the progress
    signal.
    """

    index: int  # window ordinal (0-based, ascending)
    start: int
    stop: int
    n_passed: int
    cols: dict  # branch -> survivor array ({} when nothing passed)
    jagged: dict  # jagged branch -> counts branch, for `cols`
    decision: str = SCAN  # zone-map kind this window resolved as


@dataclass
class BatchWindowPartial:
    """One basket window of a shared scan, streamed per tenant.

    ``tenants[i]`` is tenant *i*'s :class:`WindowPartial` for this window
    — survivor columns exactly as they will land in that tenant's final
    output, so per-tenant unions of streamed partials are bit-identical
    to the batch result by construction (DESIGN.md §12).
    """

    index: int
    start: int
    stop: int
    tenants: list  # per tenant, request order: WindowPartial


@dataclass
class SharedScanResult:
    """Batch result of one shared scan over N tenant queries."""

    results: list[SkimResult]  # per-query, in request order
    shared_stats: FetchStats  # the single phase-1 pass (union branches)
    shared_breakdown: Breakdown  # fetch/decode of that pass (+ modeled link)
    naive_phase1_bytes: int  # what N independent scans would have fetched
    wall_s: float = 0.0

    @property
    def n_queries(self) -> int:
        return len(self.results)

    @property
    def saved_bytes(self) -> int:
        """Phase-1 bytes the shared scan avoided vs N independent skims."""
        return self.naive_phase1_bytes - self.shared_stats.bytes_fetched

    @property
    def amortization(self) -> float:
        """naive/shared phase-1 byte ratio (>= 1; ~N for similar queries)."""
        return self.naive_phase1_bytes / max(self.shared_stats.bytes_fetched, 1)


def drain(gen):
    """Drive a partial-yielding executor generator to its final result.

    The streaming executors are generators that yield
    :class:`WindowPartial` (or :class:`BatchWindowPartial`) per window and
    *return* the final result object — ``drain`` is the synchronous
    caller's one-liner to discard the stream and keep the result.
    """
    while True:
        try:
            next(gen)
        except StopIteration as stop:
            return stop.value


def _solo(batch):
    """A batch of one's stream as a solo run's: its tenant's partial per
    window, then its one :class:`SkimResult`.  Closing this stream closes
    the batch (cancellation reaches the window loop)."""
    try:
        while True:
            try:
                part = next(batch)
            except StopIteration as stop:
                return stop.value.results[0]
            yield part.tenants[0]
    finally:
        batch.close()


@dataclass
class _Tenant:
    """One query's state across the window loop."""

    plan: SkimPlan
    program: object = None  # compiled predicate program (fused runs)
    ex: CascadeExecutor | None = None  # its cascade, when cascaded
    b: Breakdown = field(default_factory=Breakdown)
    p2: FetchStats = field(default_factory=FetchStats)  # phase-2 fetches
    # (start, stop, n_passed, survivor columns) of every window with
    # survivors: what the output store is built from at the end
    pieces: list = field(default_factory=list)
    jagged: dict = field(default_factory=dict)
    # per-window survivor ledger: (start, stop, n_passed) for EVERY
    # window, survivors or not — the mergeable-result contract the
    # cluster coordinator splits shard outputs with (DESIGN.md §5)
    rows: list = field(default_factory=list)
    n_passed: int = 0
    pad_K: int = 0  # grows monotonically so padded shapes (and compiled
    # kernels) stay stable once the max multiplicity has been seen

    @property
    def decisions(self):
        """Zone-map decisions (DESIGN.md §9), one per window, or None when
        pruning is off / nothing was provable."""
        return self.plan.window_decisions

    def kind(self, wi: int) -> str:
        decisions = self.decisions
        return decisions[wi].decision if decisions is not None else SCAN


def _union(lists) -> list[str]:
    """First-seen-order union of branch lists."""
    return list(dict.fromkeys(name for names in lists for name in names))


def _tenant_attr(solo: bool, i: int) -> dict:
    """Span attrs naming the tenant — a shared scan's only."""
    return {} if solo else {"tenant": i}


class _Timer:
    def __init__(self, breakdown: Breakdown, key: str):
        self.b, self.k = breakdown, key

    def __enter__(self):
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        setattr(self.b, self.k, getattr(self.b, self.k) + time.perf_counter() - self.t0)


def _decode_branches(
    store: EventStore,
    names: list[str],
    start: int,
    stop: int,
    breakdown: Breakdown,
    stats: FetchStats,
    coalesce: bool,
    preloaded: dict[str, np.ndarray] | None = None,
    tracer=None,
) -> dict[str, np.ndarray]:
    """Fetch+decode a branch set for an event range; returns columnar data.

    Jagged branches come back as flat value arrays; counts branches carry
    the structure (the evaluator uses ``n<Coll>``).  ``preloaded`` supplies
    counts branches already decoded in an earlier stage.
    """
    tr = tracer if tracer is not None else NULL_TRACER
    data: dict[str, np.ndarray] = dict(preloaded or {})
    # counts branches must be sliced before the jagged values they describe
    order = sorted(names, key=lambda n: 0 if not store.branches[n].jagged else 1)
    # one coalesced read round for the whole branch set (TTreeCache model;
    # the store owns the request accounting — DESIGN.md §2b)
    fsid = tr.begin("fetch", kind="fetch", branches=len(order))
    window = store.fetch_window(order, start, stop, stats=stats, coalesce=coalesce)
    tr.end(fsid, bytes=stats.bytes_fetched)
    # decode spans name their tier: "decode_device" when the store's
    # backend-selected batch decode runs on the accelerator (bitpack
    # planes crossing the host->device boundary compressed, DESIGN.md §16)
    dkind = (
        "decode_device"
        if store.resolved_decode_backend() == "device"
        and store.codec == "bitpack"
        else "decode"
    )
    dsid = tr.begin("decode", kind=dkind)
    # one decode round for the whole branch set; only the slicing below
    # needs the counts branches first
    with _Timer(breakdown, "decompress"):
        decoded = store.decode_round(
            {name: [blob for _, blob in window[name]] for name in order}, tracer=tr
        )
    with _Timer(breakdown, "deserialize"):
        for name in order:
            br = store.branches[name]
            parts = []
            for (meta, _), vals in zip(window[name], decoded[name]):
                if not br.jagged:
                    lo = max(start - meta.first_entry, 0)
                    hi = min(stop - meta.first_entry, meta.n_entries)
                    parts.append(vals[lo:hi])
                else:
                    counts = data[br.counts_branch]
                    # basket-local event slice using already-decoded counts
                    b0 = max(start, meta.first_entry)
                    b1 = min(stop, meta.first_entry + meta.n_entries)
                    gc = counts[b0 - start : b1 - start].astype(np.int64)
                    # leading events of this basket that precede `start`
                    if meta.first_entry < start:
                        lead = store.read_flat(
                            br.counts_branch, meta.first_entry, start
                        ).astype(np.int64).sum()
                    else:
                        lead = 0
                    parts.append(vals[lead : lead + gc.sum()])
            data[name] = (
                np.concatenate(parts) if parts else np.empty(0, dtype=br.np_dtype())
            )
    tr.end(dsid)
    return data


def _pipeline_schedule(
    records: list[dict], link: NetworkModel, depth: int = 2
) -> float:
    """Exact event-driven schedule of the double-buffered executor.

    One load worker (modeled link fetch + measured decode per window)
    runs ahead of one process worker (measured filter + phase-2 fetch and
    compute), with at most ``depth`` windows in flight — load of window
    ``i`` cannot start before window ``i - depth`` finished processing.
    Returns the makespan of the window loop; the serial equivalent is the
    plain sum of all stage terms (DESIGN.md §4b).
    """
    load_free = 0.0  # when the load worker is next available
    proc_free = 0.0  # when the process worker is next available
    done: list[float] = []  # per-window processing completion times
    for i, r in enumerate(records):
        load_t = (
            link.transfer_time(r["load_bytes"], r["load_requests"])
            + r["load_compute"]
        )
        start = load_free if i < depth else max(load_free, done[i - depth])
        load_done = start + load_t
        proc_t = r.get("proc_compute", 0.0) + link.transfer_time(
            r.get("p2_bytes", 0), r.get("p2_requests", 0)
        )
        proc_free = max(proc_free, load_done) + proc_t
        done.append(proc_free)
        load_free = load_done
    return proc_free


def _window_phase2(
    store,
    plan: SkimPlan,
    start: int,
    stop: int,
    mask: np.ndarray,
    dev_cols: dict,
    loaded: dict,
    breakdown: Breakdown,
    stats: FetchStats,
    coalesce: bool,
    tracer=None,
) -> tuple[dict, dict]:
    """Phase 2 for one surviving window outside the basket ledger: fetch
    the output-only branches and select survivor columns.

    The fetch set is every output branch not already decoded: for scanned
    windows ``loaded`` holds the filter branches, so this is exactly the
    output-only set; for zone-map *accept-all* windows nothing was loaded
    in phase 1 and the whole output set moves here in one round
    (DESIGN.md §9)."""
    need2 = [x for x in plan.output_branches if x not in loaded]
    data2 = _decode_branches(
        store, need2, start, stop, breakdown, stats, coalesce, preloaded=loaded,
        tracer=tracer,
    )
    full = {**loaded, **data2}
    with _Timer(breakdown, "deserialize"):
        cols, jagged = _select_columns(
            {k2: full[k2] for k2 in plan.output_branches if k2 not in dev_cols},
            mask,
            store,
        )
        # payload columns come straight off the fused kernel, already
        # survivor-compacted (bit-identical to arr[mask])
        cols.update(dev_cols)
    return cols, jagged


def _rows_materialize(data: dict[str, np.ndarray], store, n: int) -> list:
    """Legacy deserialization: per-event row objects (the C++-object analogue).

    This is what makes unoptimized client-side filtering CPU-bound: every
    branch of every event becomes a Python-level object before the filter
    runs (paper: 240.4 s deserialize for LZ4 client-side).
    """
    offsets = {}
    for name in data:
        br = store.branches.get(name)
        if br is not None and br.jagged:
            counts = data[br.counts_branch].astype(np.int64)
            offsets[name] = np.concatenate([[0], np.cumsum(counts)])
    rows = []
    for i in range(n):
        row = {}
        for name, arr in data.items():
            br = store.branches.get(name)
            if br is not None and br.jagged:
                off = offsets[name]
                row[name] = arr[off[i] : off[i + 1]]
            else:
                row[name] = arr[i]
        rows.append(row)
    return rows


def _select_columns(
    data: dict[str, np.ndarray], mask: np.ndarray, store
) -> tuple[dict[str, np.ndarray], dict[str, str]]:
    """Apply an event mask to columnar data -> (columns, jagged map)."""
    cols: dict[str, np.ndarray] = {}
    jagged: dict[str, str] = {}
    for name, arr in data.items():
        br = store.branches.get(name)
        if br is not None and br.jagged:
            counts = data[br.counts_branch].astype(np.int64)
            obj_mask = np.repeat(mask, counts)
            cols[name] = arr[obj_mask]
            jagged[name] = br.counts_branch
        else:
            cols[name] = arr[mask]
    return cols, jagged


def _write_output(
    store: EventStore,
    pieces: list,
    names: list[str],
    jagged: dict,
    breakdown: Breakdown,
    tracer=NULL_TRACER,
    **attrs,
) -> EventStore:
    """Build the job's output store from its windows' survivors, under a
    ``write`` span: ``pieces`` lists ``(start, stop, n_passed, cols)`` per
    window with survivors.  Output baskets that are exactly one wholly
    kept input basket are cloned, the rest encoded
    (:meth:`EventStore.from_survivors`); the span counts both."""
    osid = tracer.begin("write", kind="write", **attrs)
    with _Timer(breakdown, "write"):
        out, cloned, encoded = EventStore.from_survivors(store, pieces, names, jagged)
    tracer.end(osid, baskets_cloned=cloned, baskets_encoded=encoded)
    return out


class SkimEngine:
    """Runs :class:`Query` objects against an :class:`EventStore`: one
    query in one of the paper's four execution modes, or a batch of
    tenant queries over one shared pass (DESIGN.md §4c).

    ``fused`` / ``pipeline`` control the ``near_data`` executor only (the
    DPU analogue is where the fast path lives): ``fused=True`` evaluates
    the compiled predicate + stream compaction as one pass per window on
    the backend's best executor, and ``pipeline`` double-buffers window
    fetch+decode behind filtering — ``True`` runs the serial schedule and
    computes the exact double-buffered makespan from per-window records
    (``extras["pipeline_total"]``; compute stages stay cleanly
    measurable), ``"threads"`` additionally runs the real
    :class:`~repro.data.store.WindowPrefetcher` worker (wall-clock
    overlap on hosts with spare cores; stage timings then include
    contention).  The other three modes always run the reference serial
    paths so the paper comparison stays honest.

    Note: any fused or pipelined configuration preloads *all* filter
    branches per window (one coalesced TTreeCache round), trading the
    staged evaluator's early-discard byte savings for batched I/O — so
    byte accounting differs slightly from the lazy staged path when
    whole windows die at an early stage.  The seed-exact reference for
    accounting comparisons is ``fused=False, pipeline=False``.
    """

    def __init__(
        self,
        store: EventStore,
        input_link: NetworkModel = WAN_1G,
        output_link: NetworkModel | None = None,
        chunk_events: int | None = None,
        decode_fn=None,
        fused: bool = True,
        pipeline: bool | str = True,
        near_input_link: NetworkModel = PCIE_128G,
        prune: bool = True,
        cascade: bool = True,
        tracer=None,
        device_batch: int | None = None,
        fused_backend: str | None = None,
    ):
        self.store = store
        self.input_link = input_link
        self.output_link = output_link or input_link
        self.chunk_events = chunk_events or store.basket_events
        # near-data mode may plug in the Pallas/vectorized decoder
        self.decode_fn = decode_fn
        self.fused = fused
        self.pipeline = pipeline
        # what the DPU analogue reads its input baskets over: PCIe Gen3
        # x16 by default, or an SSD-class tier (e.g. LOCAL_DISK) to model
        # near-storage fetch that the prefetcher actually has to hide
        self.near_input_link = near_input_link
        # zone-map predicate pushdown (DESIGN.md §9): classify each basket
        # window from encode-time stats and skip fetch+decode for windows
        # provably empty (or provably all-surviving).  ``False`` is the
        # reference path every pruned run must stay bit-identical to.
        self.prune = prune
        # cascaded phase-1 execution (DESIGN.md §11): run the fused
        # near-data phase 1 as a cost-ordered cascade of per-node stages,
        # fetching each stage's branches only for baskets still alive.
        # ``False`` restores the PR-4 full-preload path (the accounting
        # reference), bit-identical on survivors either way.
        self.cascade = cascade
        # default span sink (repro.obs.trace); the no-op tracer unless a
        # caller opts in — per-call ``tracer=`` overrides take precedence
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # device-resident batched cascade (DESIGN.md §16): group this many
        # cascaded SCAN windows per device dispatch — O(windows/B) stage
        # dispatches instead of O(windows), with survivor masks living on
        # device between stages.  ``None``/1 keeps the per-window path.
        if device_batch is not None and int(device_batch) < 1:
            raise ValueError(f"device_batch must be >= 1, got {device_batch}")
        self.device_batch = int(device_batch) if device_batch else None
        # forced fused-evaluator backend ("pallas"/"xla"/"host"); ``None``
        # resolves per backend (pallas on TPU, host interpreter elsewhere)
        if fused_backend not in (None, "pallas", "xla", "host"):
            raise ValueError(f"unknown fused backend {fused_backend!r}")
        self.fused_backend = fused_backend

    # -- public API ----------------------------------------------------------

    def run(
        self,
        query: Query | dict | str,
        mode: str = "near_data",
        fused: bool | None = None,
        pipeline: bool | str | None = None,
        prune: bool | None = None,
        cascade: bool | None = None,
        tracer=None,
    ) -> SkimResult:
        plans, args = self._prepare(
            [query], mode, fused, pipeline, prune, cascade, tracer
        )
        if args is None:  # client_plain: the one-pass legacy path
            return self._run_client_plain(plans[0])
        return drain(self._iter_two_phase(plans, **args)).results[0]

    def iter_run(
        self,
        query: Query | dict | str,
        mode: str = "near_data",
        fused: bool | None = None,
        pipeline: bool | str | None = None,
        prune: bool | None = None,
        cascade: bool | None = None,
        tracer=None,
    ):
        """Streaming form of :meth:`run`: a generator yielding one
        :class:`WindowPartial` per basket window as its ledger entry
        completes, and *returning* the final :class:`SkimResult` (via
        ``StopIteration.value``; :func:`drain` recovers it).

        This is the cooperative execution surface the async job service
        schedules on (DESIGN.md §12): window boundaries are the
        cancellation points, and the stream of partials is the partial-
        result feed.  Identical accounting and output to :meth:`run` by
        construction — both run the window loop over a batch of one.
        ``client_plain`` has no window loop and cannot stream.
        """
        plans, args = self._prepare(
            [query], mode, fused, pipeline, prune, cascade, tracer
        )
        if args is None:
            raise ValueError("client_plain is a one-pass mode; nothing to stream")
        return _solo(self._iter_two_phase(plans, **args))

    def run_batch(
        self, queries: list[Query | dict | str], tracer=None
    ) -> SharedScanResult:
        return drain(self.iter_batch(queries, tracer=tracer))

    def iter_batch(self, queries: list[Query | dict | str], tracer=None):
        """N tenant queries over ONE near-data pass of the store: a
        generator yielding one :class:`BatchWindowPartial` per basket
        window (every tenant's ledger entry for that window together,
        since the scan is shared) and returning the final
        :class:`SharedScanResult`.  Per-tenant outputs are bit-identical
        to running each query alone; a batch of one *is* the solo run.
        Window boundaries are the job service's cancellation points; a
        tenant cancelled mid-batch simply stops collecting its partials —
        the shared pass is one fetch either way (DESIGN.md §12)."""
        plans, args = self._prepare(queries, "near_data", tracer=tracer)
        return self._iter_two_phase(plans, **args)

    def _prepare(
        self,
        queries: list,
        mode: str,
        fused: bool | None = None,
        pipeline: bool | str | None = None,
        prune: bool | None = None,
        cascade: bool | None = None,
        tracer=None,
    ) -> tuple[list[SkimPlan], dict | None]:
        """Argument resolution + planning for every entry point.

        Returns ``(plans, two_phase_kwargs)``; ``None`` kwargs means
        client_plain (the legacy one-pass path)."""
        tr = tracer if tracer is not None else self.tracer
        queries = [q if isinstance(q, Query) else parse_query(q) for q in queries]
        do_prune = (self.prune if prune is None else bool(prune)) and (
            mode != "client_plain"  # full-scan legacy mode: nothing to push down
        )
        use_fused = self.fused if fused is None else fused

        def wants_cascade(query: Query) -> bool:
            # explicit call arg > query flag > engine default; the cascade
            # lives on the near-data fused fast path only (the other modes
            # are the paper's fixed comparison points)
            flag = cascade
            if flag is None:
                flag = query.cascade if query.cascade is not None else self.cascade
            return bool(flag) and mode == "near_data" and use_fused

        plan_t0 = tr.now()
        plans = [
            plan_skim(
                q, self.store, window_events=self.chunk_events, prune=do_prune,
                cascade=wants_cascade(q),
            )
            for q in queries
        ]
        args = dict(tracer=tr, plan_t=(plan_t0, tr.now()), mode=mode)
        if mode == "client_plain":
            return plans, None
        if mode == "client_opt":
            return plans, dict(args, link=self.input_link, coalesce=True)
        if mode == "server_side":
            return plans, dict(args, link=LOCAL_DISK, coalesce=False)
        if mode == "near_data":
            prefetch = self.pipeline if pipeline is None else pipeline
            if prefetch not in (False, True, "threads"):
                raise ValueError(
                    f"pipeline must be False, True, or 'threads', got {prefetch!r}"
                )
            return plans, dict(
                args, link=self.near_input_link, coalesce=True,
                fused=use_fused, prefetch=prefetch,
            )
        raise ValueError(f"unknown mode {mode}")

    # -- legacy client-side (Fig. 2b) -----------------------------------------

    def _run_client_plain(self, plan: SkimPlan) -> SkimResult:
        store, b, stats = self.store, Breakdown(), FetchStats()
        n = store.n_events

        data = _decode_branches(
            store, plan.output_branches, 0, n, b, stats, coalesce=True
        )
        # legacy deserialization: build per-event rows for EVERY branch
        with _Timer(b, "deserialize"):
            rows = _rows_materialize(data, store, n)

        with _Timer(b, "filter"):
            mask = np.ones(n, dtype=bool)
            for _, stage in plan.query.stages():
                mask &= eval_stage(stage, data, n)
            del rows

        cols, jagged = _select_columns(data, mask, store)
        k = int(mask.sum())
        out = _write_output(store, [(0, n, k, cols)], list(cols), jagged, b)

        b.fetch = self.input_link.transfer_time(stats.bytes_fetched, stats.requests)
        b.output_transfer = 0.0  # filtering ran at the client already
        compute = b.decompress + b.deserialize + b.filter + b.write
        return SkimResult(
            "client_plain", out, n, k, b, stats, plan,
            busy_fraction=compute / max(b.total(), 1e-12),
        )

    # -- two-phase model (client_opt / server_side / near_data) ---------------

    def _iter_two_phase(
        self,
        plans: list[SkimPlan],
        mode: str,
        link: NetworkModel,
        coalesce: bool,
        fused: bool = False,
        prefetch: bool | str = False,
        tracer=None,
        plan_t: tuple | None = None,
    ):
        """The window loop over N >= 1 tenant plans: yields a
        :class:`BatchWindowPartial` per window, returns the
        :class:`SharedScanResult`.

        Every decision that differs between a solo run (N = 1) and a
        shared scan (N > 1) is taken here from ``solo`` — DESIGN.md §4c
        lists them: the load set, ACCEPT_ALL's phase 2, where phase-1
        bytes are charged, the spans, and the staged evaluator's lazy
        fetch."""
        tracer = tracer if tracer is not None else NULL_TRACER
        store, chunk = self.store, self.chunk_events
        n = store.n_events
        t_start = time.perf_counter()
        solo = len(plans) == 1

        # the root span stays open across the whole generator; each child
        # span closes before the window's partial yields, so a consumer
        # observing the stream never sees a half-open child
        if solo:
            rsid = tracer.begin(
                "query", kind="query", mode=mode, n_events=n, fused=fused
            )
        else:
            rsid = tracer.begin(
                "batch", kind="query", n_tenants=len(plans), n_events=n
            )
        if plan_t is not None:
            tracer.add_span("plan", kind="plan", t0=plan_t[0], t1=plan_t[1])

        dispatches0 = None
        if fused:
            # one-time executor warm-up (module imports + backend init)
            # outside the stage timers: measured stages report steady-state
            # compute, not interpreter start-up (DESIGN.md §2c)
            import jax

            from repro.core.neardata import (
                fused_window_skim,
                program_eval_np,
                window_pad_K,
            )
            from repro.kernels.ops import dispatch_stats

            jax.default_backend()
            dispatches0 = dispatch_stats()["dispatches"]
        # cascaded phase 1 (DESIGN.md §11): one executor per tenant owns
        # its adaptive stage order; the load stage fetches only the pinned
        # head stages, later stages fetch alive baskets on demand
        tenants = [
            _Tenant(
                plan=p,
                program=p.compiled_program() if fused else None,
                ex=CascadeExecutor(
                    p, store, coalesce=coalesce, tracer=tracer,
                    backend=self.fused_backend,
                )
                if p.cascade is not None
                else None,
            )
            for p in plans
        ]
        all_cascade = bool(tenants) and all(t.ex is not None for t in tenants)
        any_cascade = any(t.ex is not None for t in tenants)
        # the filter union prices pruned windows and the cascade's savings
        # (what a full filter preload would move); ``heads`` is what the
        # load stage fetches for a scanned window: each tenant's pinned
        # head stage when cascading, its full filter set otherwise
        filter_union = _union(t.plan.filter_branches for t in tenants)
        heads = _union(
            t.ex.head_branches if t.ex is not None else t.plan.filter_branches
            for t in tenants
        )
        use_threads = prefetch == "threads"
        # a shared scan always loads its union up front; a solo run loads
        # in a separate stage only on the fused or pipelined paths
        preload = fused or bool(prefetch) or not solo

        def load_set(wi: int):
            """Window ``wi``'s load set: ``None`` when every tenant prunes
            it (DESIGN.md §9), else the branches the load stage fetches."""
            kinds = [t.kind(wi) for t in tenants]
            if all(k == PRUNE for k in kinds):
                return None
            if solo and kinds[0] == ACCEPT_ALL:
                # every event survives: the one coalesced round that phase
                # 2 would pay moves into the load stage
                return tenants[0].plan.output_branches
            # a shared scan loads its heads even where every tenant
            # accepts: their phase 2 reuses the decoded union
            return heads

        loads = [load_set(wi) for wi in range(-(-n // chunk) if n else 0)]

        def load_window(start: int, stop: int):
            """Fetch + decode one window's load set (in "threads" mode
            this runs in the prefetch worker; all accounting is
            window-local and merged in window order by the consumer, so
            pipelined byte/request stats are identical to the serial
            schedule)."""
            names = loads[start // chunk]
            lb, ls = Breakdown(), FetchStats()
            if names is None:
                # provably no survivor for anyone: neither phase happens;
                # account what the skipped filter round would have moved
                nbytes, nb = store.range_comp_bytes(filter_union, start, stop)
                ls.skip(nbytes, coalesced_requests(nbytes, nb, coalesce))
                return None, lb, ls
            if not preload:
                return None, lb, ls
            # the prefetch worker thread must not touch the consumer's
            # span stack; its loads go untraced in "threads" mode (the
            # serial schedules trace them as load_window spans)
            ltr = NULL_TRACER if use_threads else tracer
            lsid = ltr.begin(
                "load_window", kind="load_window", window=start // chunk
            )
            data = _decode_branches(
                store, names, start, stop, lb, ls, coalesce, tracer=ltr
            )
            ltr.end(lsid, bytes=ls.bytes_fetched)
            return data, lb, ls

        def window_ledger(wi: int, start: int, stop: int) -> dict[str, set]:
            """The window-shared basket ledger (DESIGN.md §11): every
            (branch, basket) pair moves at most once per window across
            all tenants and both phases; the loaded heads are in it."""
            ledger: dict[str, set] = {}
            if any_cascade and loads[wi] is heads:
                mark_fetched(store, heads, start, stop, ledger)
            return ledger

        # device-batched cascade grouping (DESIGN.md §16): consume windows
        # that loaded the heads in groups of ``device_batch``, run each
        # tenant's cascade ONCE per group (one device dispatch per stage
        # per group, survivor masks device-resident between stages), then
        # replay the outcomes through the per-window loop below.  Other
        # windows pass through unbatched and close the open group.
        G = self.device_batch if all_cascade else None
        src = enumerate(WindowPrefetcher(n, chunk, load_window, enabled=use_threads))
        pending: dict[tuple[int, int], tuple] = {}
        ledgers: dict[int, dict] = {}

        def flush(buf: list):
            for wi, (start, stop, _payload) in buf:
                ledgers[wi] = window_ledger(wi, start, stop)
            for i, t in enumerate(tenants):
                sel = [item for item in buf if t.kind(item[0]) == SCAN]
                if not sel:
                    continue
                entries = [
                    (start, stop, data, Breakdown(), FetchStats(), ledgers[wi])
                    for wi, (start, stop, (data, _lb, _ls)) in sel
                ]
                outs = t.ex.run_window_batch(entries, pad_B=G)
                for (wi, _w), entry, out in zip(sel, entries, outs):
                    pending[i, wi] = (out, entry[3], entry[4])
            items = list(buf)
            buf.clear()
            return items

        def grouped():
            if not G or G <= 1:
                yield from src
                return
            buf: list = []
            for item in src:
                if loads[item[0]] is heads:
                    buf.append(item)
                    if len(buf) == G:
                        yield from flush(buf)
                else:
                    yield from flush(buf)
                    yield item
            yield from flush(buf)

        shared_b, p1 = Breakdown(), FetchStats()  # the load stage + phase 1
        # per-window load/process records feeding the explicit pipeline
        # schedule model (DESIGN.md §4b); solo runs only
        records: list[dict] = []

        def tenant_window(i: int, t: _Tenant, wi, start, stop, data, ledger):
            """One tenant's window: phase 1 by decision kind, then phase
            2.  Returns its partial and whether its baskets went through
            the window ledger."""
            m = stop - start
            plan, kind = t.plan, t.kind(wi)
            # window-local processing breakdown/stats (merged into the run
            # totals below; also feeds the pipeline schedule model)
            wb, w1s, w2s = Breakdown(), FetchStats(), FetchStats()
            loaded = data if data is not None else {}
            dev_cols: dict[str, np.ndarray] = {}
            known: dict = {}
            via_ledger = False
            if kind == PRUNE:
                # provably no survivor: phase 1 AND phase 2 never happen
                mask = np.zeros(m, dtype=bool)
            elif kind == ACCEPT_ALL:
                # provably all survive: no predicate fetch or eval
                mask = np.ones(m, dtype=bool)
                if solo:
                    # the output set moved in the load stage (or moves in
                    # phase 2); filter-only branches never move at all
                    dec = t.decisions[wi]
                    w1s.skip(dec.extra_bytes, 0 if coalesce else dec.extra_baskets)
                elif t.ex is not None:
                    # phase 2 flows through the window ledger (the fused
                    # payload shortcut needs the full filter preload the
                    # cascade deliberately no longer does)
                    via_ledger, known = True, loaded
                elif fused and plan.filter_branches:
                    # the fused executor's decision short-circuit skips
                    # predicate eval and passes the payload through whole
                    with _Timer(wb, "filter"):
                        mask, dev_cols = fused_window_skim(
                            loaded, t.program, store,
                            payload_branches=plan.payload_branches,
                            decision=ACCEPT_ALL, backend=self.fused_backend,
                        )
            elif t.ex is not None:
                # ---- phase 1 (cascaded path, DESIGN.md §11): stages run
                # cheapest-and-most-selective-first; stage k fetches its
                # branches only for baskets still alive after stage k-1 ----
                if (i, wi) in pending:
                    # batched path: the cascade already ran for this
                    # window's group — adopt its outcome and per-window
                    # ledgers (byte/time accounting is window-local in the
                    # batch too, so totals match the per-window path)
                    outcome, cwb, w1s = pending.pop((i, wi))
                    wb.merge(cwb)
                else:
                    outcome = t.ex.run_window(
                        start, stop, data, wb, w1s, ledger=ledger
                    )
                mask = outcome.mask
                via_ledger, known = True, {**loaded, **outcome.full_loaded}
            elif fused:
                # ---- phase 1 (fused path): one pass evaluates the
                # compiled predicate AND compacts [index]+payload rows ----
                if not plan.filter_branches:
                    # no present branch feeds the predicate: the program is
                    # constant — all-true for a selection-free projection,
                    # all-false when only absent-era trigger ORs remain
                    mask = program_eval_np(loaded, t.program, m)
                else:
                    t.pad_K = max(t.pad_K, window_pad_K(loaded, t.program, store))
                    ksid = (
                        tracer.begin("kernel", kind="kernel", window=wi)
                        if solo
                        else None
                    )
                    with _Timer(wb, "filter"):
                        mask, dev_cols = fused_window_skim(
                            loaded, t.program, store,
                            payload_branches=plan.payload_branches,
                            K=t.pad_K,
                            pad_to=chunk,
                            backend=self.fused_backend,
                            tracer=tracer,
                        )
                    if solo:
                        tracer.end(ksid)
            else:
                # ---- phase 1: staged filter over filter-criteria branches;
                # a solo run without a load stage fetches each stage's
                # branches lazily, after the hierarchical early discard ----
                mask = np.ones(m, dtype=bool)
                loaded = dict(loaded)
                for stage_name, stage in plan.query.stages():
                    if not stage:
                        continue
                    if not mask.any():
                        break  # early discard: skip later stages
                    need = [
                        x
                        for x in sorted(plan.query.stage_branches(stage_name))
                        if x not in loaded and x in store.branches
                    ]
                    need = [
                        x for x in with_counts_branches(need, store) if x not in loaded
                    ]
                    if need:
                        loaded.update(
                            _decode_branches(
                                store, need, start, stop, wb, w1s, coalesce,
                                preloaded=loaded,
                            )
                        )
                    with _Timer(wb, "filter"):
                        mask &= eval_stage(stage, loaded, m)

            k = int(mask.sum())
            t.rows.append((start, stop, k))
            part = WindowPartial(
                index=wi, start=start, stop=stop, n_passed=k,
                cols={}, jagged={}, decision=kind,
            )
            if k:
                t.n_passed += k
                p2sid = tracer.begin(
                    "phase2", kind="phase2", **_tenant_attr(solo, i), window=wi
                )
                if via_ledger:
                    # ---- phase 2 through the window ledger: baskets a
                    # stage (or an earlier tenant) moved are not re-paid ----
                    full = t.ex.fetch_full(
                        plan.output_branches, start, stop, wb, w2s, ledger,
                        known=known,
                    )
                    with _Timer(wb, "deserialize"):
                        cols, jagged = _select_columns(
                            {k2: full[k2] for k2 in plan.output_branches},
                            mask, store,
                        )
                else:
                    # ---- phase 2: output-only branches, survivors only ----
                    cols, jagged = _window_phase2(
                        store, plan, start, stop, mask, dev_cols, loaded, wb,
                        w2s, coalesce, tracer=tracer,
                    )
                tracer.end(p2sid, bytes=w2s.bytes_fetched)
                t.jagged.update(jagged)
                t.pieces.append((start, stop, k, cols))
                part.cols, part.jagged = cols, jagged
            t.b.merge(wb)
            p1.merge(w1s)
            t.p2.merge(w2s)
            if records:
                records[-1].update(
                    {
                        "proc_compute": wb.decompress + wb.deserialize + wb.filter,
                        # cascaded stage fetches are non-overlapped fetch in
                        # the schedule, same currency as phase 2
                        "p2_bytes": w2s.bytes_fetched + w1s.bytes_fetched,
                        "p2_requests": w2s.requests + w1s.requests,
                    }
                )
            return part, via_ledger

        t_phase = time.perf_counter()
        for wi, (start, stop, (data, lb, ls)) in grouped():
            shared_b.merge(lb)
            p1.merge(ls)
            if solo and preload:
                records.append(
                    {
                        "load_bytes": ls.bytes_fetched,
                        "load_requests": ls.requests,
                        "load_compute": lb.decompress + lb.deserialize,
                    }
                )
            wattrs = {"decision": tenants[0].kind(wi)} if solo else {}
            wsid = tracer.begin(f"window[{wi}]", kind="window", index=wi, **wattrs)
            ledger = ledgers.pop(wi, None)
            if ledger is None:
                ledger = window_ledger(wi, start, stop)
            parts, via = [], False
            for i, t in enumerate(tenants):
                part, used = tenant_window(i, t, wi, start, stop, data, ledger)
                parts.append(part)
                via = via or used
            if all_cascade and via:
                # savings vs the filter-union preload, ledgered AFTER every
                # tenant's phase 2: a filter basket counts as skipped only
                # if nothing in the window ever moved it (phase 2
                # re-fetches dead baskets of filter∩output branches for
                # surviving windows, which must not be credited)
                p1.cascade_bytes_skipped += unfetched_bytes(
                    store, filter_union, start, stop, ledger
                )
            # the window's ledger entry is complete: stream it.  A caller
            # that stops consuming here (cancellation) has paid exactly
            # the windows it saw — the accounting above is window-local.
            tracer.end(wsid, n_passed=sum(p.n_passed for p in parts))
            try:
                yield BatchWindowPartial(
                    index=wi, start=start, stop=stop, tenants=parts
                )
            except GeneratorExit:
                # cancelled mid-stream: close the root so the partial
                # trace still exports as a well-formed tree
                tracer.end(
                    rsid, cancelled=True,
                    n_passed=sum(t.n_passed for t in tenants),
                )
                raise
        phase_wall = time.perf_counter() - t_phase

        results = []
        for i, t in enumerate(tenants):
            if solo:
                # a batch of one owns its pass: the load stage and phase
                # 1 are its own, as in any solo run
                t.b = Breakdown.merged([shared_b, t.b])
                stats = FetchStats.merged([p1, t.p2])
            else:
                stats = t.p2
            b = t.b
            out = _write_output(
                store, t.pieces, t.plan.output_branches, t.jagged, b, tracer,
                **_tenant_attr(solo, i),
            )
            b.fetch = link.transfer_time(stats.bytes_fetched, stats.requests)
            out_bytes = out.compressed_bytes()
            if mode in ("server_side", "near_data"):
                # the filtered file crosses the WAN back to the client
                b.output_transfer = self.output_link.transfer_time(out_bytes, 1)
            report = SkimReport(
                mode=mode if solo else "shared_scan",
                fused=fused,
                pipelined=bool(prefetch) if solo else use_threads,
                prune=t.decisions is not None,
                # cascaded phase-1 ledger (DESIGN.md §11)
                cascade=t.ex is not None,
                output_bytes=out_bytes,
                window_rows=t.rows,
                # zone-map pruning ledger (DESIGN.md §9): every window the
                # analysis decided without fetching, plus the priced savings
                # mirrored in stats.bytes_skipped / requests_skipped
                pruned_windows=[
                    (d.start, d.stop, d.decision)
                    for d in t.decisions or ()
                    if d.decision != SCAN
                ],
                shared_scan=not solo,
            )
            if t.ex is not None:
                report.cascade_order = t.ex.order()
                report.cascade_stages = t.ex.state.report()
            if solo:
                # the ledger only a solo run reports.  Double-buffered
                # basket prefetch (the paper's "advanced data prefetching"
                # future work): with fetch of window i+1 overlapping
                # compute of window i, the pipeline bound is
                # max(fetch, compute) instead of their sum.
                report.overlap_total_s = (
                    max(b.fetch, b.decompress + b.deserialize + b.filter)
                    + b.write
                    + b.output_transfer
                )
                report.phase_wall_s = phase_wall
                # phase split of stats.bytes_fetched (accept-all windows
                # fold their single output round into phase 1 when
                # preloading)
                report.phase1_bytes = p1.bytes_fetched
                report.phase2_bytes = t.p2.bytes_fetched
                if t.ex is not None:
                    report.cascade_bytes_skipped = stats.cascade_bytes_skipped
                if dispatches0 is not None:
                    report.device_dispatches = (
                        dispatch_stats()["dispatches"] - dispatches0
                    )
                    report.decode_backend = store.resolved_decode_backend()
                    if G:
                        report.device_batch = G
                if records:
                    # exact double-buffered schedule from the per-window
                    # records (what the threaded prefetcher realizes)
                    report.pipeline_total_s = (
                        _pipeline_schedule(records, link)
                        + b.write
                        + b.output_transfer
                    )
            compute = b.decompress + b.deserialize + b.filter + b.write
            results.append(
                SkimResult(
                    report.mode, out, n, t.n_passed, b, stats, t.plan,
                    busy_fraction=compute / max(b.total(), 1e-12),
                    extras=report.legacy_extras(),
                    report=report,
                )
            )
        # phase-1 link time is paid once for the whole batch
        shared_b.fetch = link.transfer_time(p1.bytes_fetched, p1.requests)
        tracer.end(
            rsid, n_passed=sum(t.n_passed for t in tenants),
            bytes=p1.bytes_fetched + sum(t.p2.bytes_fetched for t in tenants),
        )
        return SharedScanResult(
            results=results,
            shared_stats=p1,
            # a batch of one has no pass beside its own result's
            shared_breakdown=Breakdown() if solo else shared_b,
            naive_phase1_bytes=sum(
                store.compressed_bytes(p.filter_branches) for p in plans
            ),
            wall_s=time.perf_counter() - t_start,
        )


def run_skim(
    store: EventStore,
    query: Query | dict | str,
    mode: str = "near_data",
    input_link: NetworkModel = WAN_1G,
    output_link: NetworkModel | None = None,
    fused: bool | None = None,
    pipeline: bool | str | None = None,
    prune: bool | None = None,
    cascade: bool | None = None,
    device_batch: int | None = None,
    fused_backend: str | None = None,
) -> SkimResult:
    return SkimEngine(
        store, input_link, output_link,
        device_batch=device_batch, fused_backend=fused_backend,
    ).run(
        query, mode, fused=fused, pipeline=pipeline, prune=prune,
        cascade=cascade,
    )
