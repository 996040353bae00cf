"""One run: set-up, the measured window, the drain, and what they left.

Set-up builds the store from the seed through the program's
``EventStore.from_arrays``, puts ``SkimService(EngineBackend(store))``
on the real clock with the configuration's settings, and warms each
(template, range) pair the window will submit with one job.  The window then drives the
service from the client side: ``submit`` as the traffic says and
``step`` until the service is idle.  Arrivals stop when the window
closes; the jobs in flight drain, at most ``DRAIN_S`` past the close.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field

from harness import traffic as tr
from harness.gen import nanoaod_columns
from harness.reference import Columns

#: how long after the window closes the jobs due in it may still finish
DRAIN_S = 60.0
ANNOTATION = "skimbench.window"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"


class RealClock:
    """The service's clock seam, on ``time.perf_counter``."""

    def now(self) -> float:
        return time.perf_counter()


class CompileCounter:
    """Traces and backend compiles JAX reports, counted as they happen."""

    def __init__(self):
        import jax

        self.traces = 0
        self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE:
            self.compiles += 1
            self.compile_s += duration
        elif event == _TRACE:
            self.traces += 1


@dataclass
class JobRecord:
    doc: dict
    job: object  # the service's SkimJob
    due: float  # perf_counter time it was due (closed loop: submitted)
    submitted: float
    cursor: int = 0  # partials already counted


@dataclass
class Run:
    cell: object
    seed: int
    seconds: float
    traced: bool
    columns: Columns
    store: object
    service: object = None
    setup_s: float = 0.0
    t0: float = 0.0  # window opens (perf_counter)
    t_close: float = 0.0  # window closes
    t_cut: float = 0.0  # first reading at or after the close
    t_end: float = 0.0  # drain over
    records: list = field(default_factory=list)
    events_in_window: int = 0
    lateness: list = field(default_factory=list)
    compiles_in_window: int = 0
    traces_in_window: int = 0
    decode_at_cut: dict = field(default_factory=dict)
    decode_at_open: dict = field(default_factory=dict)
    device_trace: object = None
    spans: list = field(default_factory=list)  # Chrome-trace events


def _decode_counters(store) -> dict:
    return {**store.decode_cache_stats(), **store.decode_backend_stats()}


def setup(cell, seed: int, seconds: float, traced: bool) -> Run:
    """Data, ingest, service and warm-up; returns the run ready to open."""
    from repro.data.store import EventStore
    from repro.serve.service import EngineBackend, SkimService

    st = cell.config["store"]
    cols, jagged = nanoaod_columns(st, seed)
    if len(cols) != st["n_branches"]:
        raise ValueError(f"generated {len(cols)} branches, configuration states {st['n_branches']}")
    store = EventStore.from_arrays(
        cols, jagged=jagged, basket_events=st["basket_events"], codec=st["codec"]
    )
    backend = EngineBackend(store, **cell.config.get("engine", {}))
    svc_cfg = cell.config["service"]
    warm = SkimService(backend, clock=RealClock(), batching=svc_cfg["batching"])
    jobs = []
    for job in tr.warmup_jobs(cell.traffic, svc_cfg["tenants"], seconds):
        jobs.append(warm.submit(tr.query(cell.traffic, cell.templates, job)))
        warm.run_until_idle()
    bad = [j for j in jobs if j.state != "DONE"]
    if bad:
        raise RuntimeError(f"warm-up job {bad[0].job_id} ended {bad[0].state}: {bad[0].error}")
    run = Run(cell, seed, 0.0, traced, Columns(cols, jagged), store)
    run.service = SkimService(
        backend, clock=RealClock(), batching=svc_cfg["batching"], tracing=traced
    )
    return run


def _submit(run: Run, job: tr.Job, due: float) -> JobRecord:
    doc = tr.query(run.cell.traffic, run.cell.templates, job)
    now = time.perf_counter()
    sj = run.service.submit(doc, tenant=f"tenant{job.tenant:02d}")
    rec = JobRecord(doc, sj, due, now)
    run.records.append(rec)
    return rec


def window(
    run: Run, seconds: float, counter: CompileCounter, profile_dir: str | None = None
) -> Run:
    """Drive the service for ``seconds`` and drain; fills ``run``."""
    import jax

    cell, svc = run.cell, run.service
    loop = cell.traffic["loop"]
    n_tenants = cell.config["service"]["tenants"]
    run.seconds = seconds
    if loop == "open":
        schedule = tr.open_schedule(cell.traffic, n_tenants, seconds)
        stream = None
    else:
        schedule = []
        stream = tr.closed_jobs(cell.traffic, n_tenants)
    ann = None
    if profile_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(ANNOTATION)
        ann.__enter__()
    c0, t0c = counter.compiles, counter.traces

    def cut(now: float) -> None:
        # the window's counters end here; so does the traced window
        nonlocal ann
        run.t_cut = now
        run.compiles_in_window = counter.compiles - c0
        run.traces_in_window = counter.traces - t0c
        run.decode_at_cut = _decode_counters(run.store)
        if ann is not None:
            ann.__exit__(None, None, None)
            ann = None

    run.decode_at_open = _decode_counters(run.store)
    run.t0 = t0 = time.perf_counter()
    close = t0 + seconds
    run.t_close = close
    open_recs: list[JobRecord] = []
    clients: list[JobRecord] = []
    if stream is not None:
        for _ in range(cell.traffic["clients"]):
            rec = _submit(run, next(stream), t0)
            clients.append(rec)
            open_recs.append(rec)
    i = 0
    closed = False
    while True:
        now = time.perf_counter()
        while i < len(schedule) and t0 + schedule[i].due <= now:
            rec = _submit(run, schedule[i], t0 + schedule[i].due)
            run.lateness.append(rec.submitted - rec.due)
            open_recs.append(rec)
            i += 1
        busy = svc.step()
        now = time.perf_counter()
        for rec in list(open_recs):
            parts = rec.job.partials
            if now <= close:
                run.events_in_window += sum(p.stop - p.start for p in parts[rec.cursor:])
            rec.cursor = len(parts)
            if rec.job.terminal:
                open_recs.remove(rec)
        if not closed and now >= close:
            closed = True
            cut(now)
        for k, rec in enumerate(clients):
            if rec.job.terminal and now < close:
                clients[k] = _submit(run, next(stream), now)
                open_recs.append(clients[k])
        if not busy and not open_recs:
            if i < len(schedule):
                time.sleep(max(0.0, t0 + schedule[i].due - time.perf_counter()))
                continue
            if closed or stream is None:
                break
        if now > close + DRAIN_S:
            break
    run.t_end = time.perf_counter()
    if not closed:  # drained before the close: the window still ran its length
        time.sleep(max(0.0, close - time.perf_counter()))
        cut(time.perf_counter())
    if profile_dir is not None:
        jax.profiler.stop_trace()
        if run.traced:
            run.spans = [e for e in svc.export_trace()["traceEvents"] if e["ph"] == "X"]
    return run


def read_trace(run: Run, profile_dir: str) -> None:
    from harness import devtrace

    path = devtrace.find_xplane(profile_dir)
    run.device_trace = devtrace.load(
        path, ANNOTATION, int(run.t0 * 1e9), int(run.t_cut * 1e9)
    )
    os.remove(path)
