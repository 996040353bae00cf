"""Reduction of a JAX profiler trace to device busy time, kernel time and
idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane; busy time is the union of their intervals
inside the traced window.  The window is located on the profiler's
clock by a host annotation that the harness opens at a known
``time.perf_counter_ns()`` reading, which also maps the program's spans
(stamped with ``perf_counter``) onto the same clock.
"""

from __future__ import annotations

import glob
import heapq
import os
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclass
class DeviceTrace:
    """Device operations of one traced window, on the profiler's clock
    (nanoseconds), clipped to ``[t0, t1)``."""

    t0: float
    t1: float
    offset_ns: float  # profiler clock minus perf_counter_ns
    n_devices: int
    starts: np.ndarray = field(default_factory=lambda: np.zeros(0))
    ends: np.ndarray = field(default_factory=lambda: np.zeros(0))
    names: list = field(default_factory=list)
    device: np.ndarray = field(default_factory=lambda: np.zeros(0, int))

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_s(self) -> float:
        """Union of operation intervals, averaged over the devices."""
        total = 0.0
        for d in range(self.n_devices):
            sel = self.device == d
            total += _union_ns(self.starts[sel], self.ends[sel])
        return total / 1e9 / max(self.n_devices, 1)

    def kernel_s(self, pattern: str) -> float:
        """Summed device time of the operations whose name matches."""
        rx = re.compile(pattern)
        hit = np.array([bool(rx.search(n)) for n in self.names], dtype=bool)
        return float((self.ends[hit] - self.starts[hit]).sum()) / 1e9

    def gaps(self) -> list[tuple[float, float]]:
        """Intervals of ``[t0, t1)`` in which no operation ran on device 0."""
        sel = self.device == 0
        order = np.argsort(self.starts[sel], kind="stable")
        s, e = self.starts[sel][order], self.ends[sel][order]
        out, cur = [], self.t0
        for a, b in zip(s, e):
            if a > cur:
                out.append((cur, a))
            cur = max(cur, b)
        if cur < self.t1:
            out.append((cur, self.t1))
        return out

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operation names that took most device time."""
        agg: dict[str, float] = {}
        for name, a, b in zip(self.names, self.starts, self.ends):
            key = short_name(name)
            agg[key] = agg.get(key, 0.0) + (b - a) / 1e9
        return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def short_name(op: str) -> str:
    """``%skim_fused.1 = (...) custom-call(...)`` -> ``skim_fused``."""
    head = op.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _union_ns(starts: np.ndarray, ends: np.ndarray) -> float:
    if len(starts) == 0:
        return 0.0
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    total, cur_s, cur_e = 0.0, s[0], e[0]
    for a, b in zip(s[1:], e[1:]):
        if a > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = a, b
        else:
            cur_e = max(cur_e, b)
    return float(total + cur_e - cur_s)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, annotation: str, perf_t0_ns: int, perf_t1_ns: int) -> DeviceTrace:
    """Read the device operations of the window ``[perf_t0_ns,
    perf_t1_ns)`` (``perf_counter_ns`` readings; ``perf_t0_ns`` taken as
    the host annotation ``annotation`` opened)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ann = None
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        for line in plane.lines:
            for ev in line.events:
                if ev.name == annotation and ann is None:
                    ann = ev.start_ns
    if ann is None:
        raise ValueError(f"annotation {annotation!r} not found in {path}")
    if not devices:
        raise ValueError(f"no TPU device plane in {path}")
    offset = ann - perf_t0_ns
    t0, t1 = perf_t0_ns + offset, perf_t1_ns + offset
    starts, ends, names, dev = [], [], [], []
    for d, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                if b <= t0 or a >= t1:
                    continue
                starts.append(max(a, t0))
                ends.append(min(b, t1))
                names.append(ev.name)
                dev.append(d)
    return DeviceTrace(
        t0, t1, offset, len(devices),
        np.asarray(starts, dtype=np.float64), np.asarray(ends, dtype=np.float64),
        names, np.asarray(dev, dtype=int),
    )


def label_gaps(gaps, spans, offset_ns: float, n: int = 10) -> list[list]:
    """Idle seconds by the innermost program span open at each gap's
    midpoint (``"outside_spans"`` where none is open), largest first.

    ``spans`` are ``(kind, t0_s, t1_s)`` on the ``perf_counter`` clock."""
    iv = sorted((t0 * 1e9 + offset_ns, t1 * 1e9 + offset_ns, kind) for kind, t0, t1 in spans)
    mids = sorted(((a + b) / 2, b - a) for a, b in gaps)
    heap: list = []
    agg: dict[str, float] = {}
    i = 0
    for mid, length in mids:
        while i < len(iv) and iv[i][0] <= mid:
            a, b, kind = iv[i]
            heapq.heappush(heap, (b - a, b, kind))
            i += 1
        while heap and heap[0][1] < mid:
            heapq.heappop(heap)
        # the shortest open span is the innermost; expired longer ones
        # are dropped lazily when they reach the top
        label = heap[0][2] if heap else "outside_spans"
        agg[label] = agg.get(label, 0.0) + length / 1e9
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]
