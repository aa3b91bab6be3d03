"""From a profiler trace to device busy time, program time and a breakdown.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
``load_events`` flattens it into :class:`Event` rows (plane, line, name,
start, duration; nanoseconds on one clock for host and device); everything
else here works on those rows, so the reduction is checked on a small
recorded trace without a chip.

- Busy time is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped
  to the traced window and averaged over the devices used.
- The traced window is the harness's own ``bench.window`` annotation on
  the host plane.
- A program's device time is the sum of its ``XLA Modules`` events whose
  name holds the jitted function's name, inside the window.
- Each idle gap of device 0 is named by the innermost host annotation of
  the benchmark (``bench.*``) or the service (its span paths) that covers
  the middle of the gap.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
# host annotations that name what the host was doing: the harness's own and
# the service's span paths (``Tracer(annotate=True)``)
ANNOTATION_PREFIXES = ("bench.", "service.", "ingest.", "query.", "window.",
                       "planner.")
BREAKDOWN_ENTRIES = 10


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load_events(xplane_path: str) -> list[Event]:
    """Every event of every plane and line of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def _union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(intervals, lo: float, hi: float):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


@dataclasses.dataclass
class TraceSummary:
    """What the readers take from one traced window."""
    window_s: float
    busy_s: float                  # averaged over the devices that ran
    devices: int
    device_ops: list               # [[op name, seconds], ...] most first
    idle_gaps: list                # [[host activity, seconds], ...]
    _modules: list                 # (name, seconds) of every module event

    def program(self, name: str) -> tuple[float, int]:
        """(device seconds, calls) of the jitted programs whose module name
        holds ``name``, inside the window."""
        hits = [s for n, s in self._modules if name in n]
        return float(sum(hits)), len(hits)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops, "idle_gaps": self.idle_gaps}


def reduce_events(events: list[Event]) -> TraceSummary:
    windows = [e for e in events if e.plane == HOST_PLANE and e.name == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    win = max(windows, key=lambda e: e.dur_ns)
    lo, hi = win.start_ns, win.end_ns

    planes = sorted({e.plane for e in events
                     if e.plane.startswith(DEVICE_PREFIX)},
                    key=lambda p: int(p[len(DEVICE_PREFIX):] or 0))
    busy_by_plane = {}
    op_time: dict[str, float] = collections.defaultdict(float)
    modules = []
    for plane in planes:
        ops = [e for e in events if e.plane == plane and e.line == OPS_LINE]
        busy_by_plane[plane] = list(_clip(
            _union((e.start_ns, e.end_ns) for e in ops), lo, hi))
        for e in ops:
            for a, b in _clip([(e.start_ns, e.end_ns)], lo, hi):
                op_time[e.name] += (b - a) / len(planes)
        for e in events:
            if e.plane == plane and e.line == MODULES_LINE:
                for a, b in _clip([(e.start_ns, e.end_ns)], lo, hi):
                    modules.append((e.name, (b - a) * 1e-9))
    ran = [p for p in planes if busy_by_plane[p]]
    busy_ns = (sum(b - a for p in ran for a, b in busy_by_plane[p]) / len(ran)
               if ran else 0.0)

    gaps: dict[str, float] = collections.defaultdict(float)
    if planes:
        busy = busy_by_plane[planes[0]]
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        notes = sorted((e for e in events if e.plane == HOST_PLANE
                        and e.name != WINDOW
                        and e.name.startswith(ANNOTATION_PREFIXES)),
                       key=lambda e: e.start_ns)
        starts = [e.start_ns for e in notes]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            covering = [e for e in notes[:bisect.bisect_right(starts, mid)]
                        if e.end_ns >= mid]
            name = (min(covering, key=lambda e: e.dur_ns).name if covering
                    else "(no annotation)")
            gaps[name] += (b - a) * 1e-9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:BREAKDOWN_ENTRIES]

    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9, devices=len(ran),
        device_ops=top({k: v * 1e-9 for k, v in op_time.items()}),
        idle_gaps=top(gaps), _modules=modules)
