"""Profiler trace -> device busy time, kernel times and idle gaps.

``Profile`` records one window with ``jax.profiler`` (Python tracer off,
host annotations only) and marks the host clock with a ``bench.align``
annotation, so device events and the program's ``repro.obs`` spans (both
read on ``time.perf_counter``) land on one clock.

Device events are the operations on the accelerator planes
(``/device:TPU:<n>``, line ``XLA Ops``; the ``XLA Modules`` line names the
jitted program each ran in).  A CPU backend has no device plane: there its
XLA client thread's events stand in, which is what the tests record.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
import tempfile
import time
from typing import List, Optional, Sequence, Tuple

import jax

ALIGN = "bench.align"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    t0: float  # host clock seconds
    t1: float

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Event]
    modules: List[Event]
    start: float
    stop: float
    n_devices: int = 1

    @property
    def window_s(self) -> float:
        return self.stop - self.start

    def _clipped(self, evs: Sequence[Event]) -> List[Tuple[Event, float, float]]:
        """Events overlapping the window, each with its clipped interval."""
        return [(e, max(e.t0, self.start), min(e.t1, self.stop)) for e in evs
                if e.t1 > self.start and e.t0 < self.stop]

    def busy_intervals(self) -> List[Tuple[float, float]]:
        """Union of every operation's and program's interval (the ops line
        of a TPU trace can miss some programs the modules line has)."""
        return _union([(a, b) for _, a, b in self._clipped(self.ops + self.modules)])

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return sum(b - a for a, b in self.busy_intervals()) / max(self.n_devices, 1)

    def op_time(self, pattern: str) -> float:
        """Device seconds of the operations whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(b - a for e, a, b in self._clipped(self.ops) if rx.search(e.name))

    def module_time(self, pattern: str) -> float:
        """Seconds in which a program whose name matches ``pattern`` ran."""
        rx = re.compile(pattern)
        return sum(b - a for a, b in _union(
            [(a, b) for e, a, b in self._clipped(self.modules) if rx.search(e.name)]))

    def top_ops(self, k: int = 10) -> List[list]:
        tot: dict = {}
        for e, a, b in self._clipped(self.ops):
            tot[e.name] = tot.get(e.name, 0.0) + (b - a)
        return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, spans: Sequence[Tuple[str, float, float]],
                  k: int = 10) -> List[list]:
        """The ``k`` longest device idle gaps, each named by the innermost
        host span (latest to open) covering the gap's midpoint."""
        busy = self.busy_intervals()
        edges = [self.start] + [x for iv in busy for x in iv] + [self.stop]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        by_start = sorted(spans, key=lambda s: s[1])
        starts = [s[1] for s in by_start]
        out = []
        for a, b in gaps[:k]:
            mid = 0.5 * (a + b)
            name = "no span open"
            for s in reversed(by_start[:bisect.bisect_right(starts, mid)]):
                if s[2] >= mid:
                    name = s[0]
                    break
            out.append([name, b - a])
        return out


_ACCELERATOR = re.compile(r"^/device:(TPU|GPU):\d+$")


def _device_lines(pd):
    """Lines of the accelerator planes (``/device:TPU:0``, ...; not the
    ``/device:CUSTOM:...`` planes a TPU trace also holds)."""
    planes = [p for p in pd.planes if _ACCELERATOR.match(p.name)]
    if planes:
        return planes, [(ln, ln.name) for p in planes for ln in p.lines]
    host = [p for p in pd.planes if p.name.startswith("/host:CPU")]
    return [], [(ln, "XLA Ops") for p in host for ln in p.lines
                if ln.name.startswith("tf_XLAPjRtCpuClient")]


def reduce_profile(path: str, align_t: float, start: float, stop: float
                   ) -> DeviceTrace:
    """Read the ``.xplane.pb`` under ``path`` onto the host clock."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    pd = ProfileData.from_file(files[-1])
    align_ns = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ALIGN:
                    align_ns = ev.start_ns
    if align_ns is None:
        raise ValueError("the alignment annotation is not in the trace")
    off = align_t - align_ns * 1e-9
    planes, lines = _device_lines(pd)
    ops, modules = [], []
    for line, kind in lines:
        dest = ops if kind == "XLA Ops" else modules if kind == "XLA Modules" else None
        if dest is None:
            continue
        for ev in line.events:
            if ev.duration_ns > 0:
                t0 = ev.start_ns * 1e-9 + off
                dest.append(Event(ev.name, t0, t0 + ev.duration_ns * 1e-9))
    return DeviceTrace(ops, modules, start, stop, n_devices=max(len(planes), 1))


class Profile:
    """Context manager: trace the enclosed window; ``result`` afterwards."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.result: Optional[DeviceTrace] = None

    def __enter__(self) -> "Profile":
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.align_t = time.perf_counter()
        with jax.profiler.TraceAnnotation(ALIGN):
            pass
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.stop = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self) -> DeviceTrace:
        try:
            self.result = reduce_profile(self.dir, self.align_t, self.start,
                                         self.stop)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.result
