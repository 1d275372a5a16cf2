"""Reduction of a profiler trace to intervals the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
``load`` flattens it into ``Event`` rows; ``reduce`` keeps

- ``ops``: device operations, from the ``XLA Ops`` line of each device
  plane, merged into busy intervals;
- ``modules``: executions of whole programs (the ``XLA Modules`` line),
  each tagged with the job span it falls in;
- ``spans``: the host spans the benchmark writes with
  ``jax.profiler.TraceAnnotation``: ``job:<service>`` around each job and
  ``traced_window`` around the traced part of the window.

All times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import bisect
from pathlib import Path
from typing import NamedTuple, Optional

DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
JOB_PREFIX = "job:"
WINDOW_SPAN = "traced_window"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


class Module(NamedTuple):
    name: str
    start: float
    end: float
    service: Optional[str]       # the job span it ran in, if any


class Reduced(NamedTuple):
    devices: int
    busy: list                   # merged (start, end) over all devices
    ops: list                    # Event rows of device ops
    modules: list                # Module rows
    jobs: list                   # (service, start, end)
    window: tuple                # (start, end) of the traced window


class Recorder:
    """The profiler on for part of the window, inside a ``traced_window``
    span.  ``stop`` may be called more than once (from the executor's event
    script, then after the window)."""

    def __init__(self, directory: Path):
        self.directory = Path(directory)
        self._span = None

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(str(self.directory))
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self, _executor=None) -> None:
        import jax

        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()

    def reduce(self) -> "Reduced":
        return reduce(load(find_xplane(self.directory)))


def find_xplane(directory: Path) -> Path:
    found = sorted(Path(directory).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: Path) -> list[Event]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return [Event(plane.name, line.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint, sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    """Length of [lo, hi] that the merged intervals cover."""
    total = 0.0
    i = max(bisect.bisect_right(merged, (lo, float("inf"))) - 1, 0)
    for s, e in merged[i:]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that the merged intervals leave uncovered."""
    out, t = [], lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _owner(jobs, t: float) -> Optional[str]:
    for svc, s, e in jobs:
        if s <= t <= e:
            return svc
    return None


def reduce(events: list[Event]) -> Reduced:
    dev_planes = {e.plane for e in events if e.plane.startswith(DEVICE_PREFIX)}
    ops = [e for e in events
           if e.plane in dev_planes and e.line == OPS_LINE and e.dur > 0]
    host = [e for e in events if not e.plane.startswith("/device:")]
    jobs = sorted((e.name[len(JOB_PREFIX):], e.start, e.end)
                  for e in host if e.name.startswith(JOB_PREFIX))
    win = [e for e in host if e.name == WINDOW_SPAN]
    if win:
        window = (win[0].start, win[0].end)
    elif jobs:
        window = (jobs[0][1], jobs[-1][2])
    else:
        window = (0.0, 0.0)
    modules = sorted(
        (Module(e.name, e.start, e.end, _owner(jobs, e.start))
         for e in events if e.plane in dev_planes and e.line == MODULES_LINE),
        key=lambda m: m.start)
    return Reduced(len(dev_planes), union((e.start, e.end) for e in ops),
                   ops, modules, jobs, window)


def device_busy_s(r: Reduced) -> float:
    """Seconds of the traced window in which some device op ran, averaged
    over the devices (the busy intervals are merged across devices, so
    with one device this is exact)."""
    if not r.devices:
        return 0.0
    return covered(r.busy, *r.window) / 1e9 / r.devices


def top_ops(r: Reduced, n: int = 10) -> list:
    total: dict[str, float] = {}
    lo, hi = r.window
    for e in r.ops:
        part = min(e.end, hi) - max(e.start, lo)
        if part > 0:
            total[e.name] = total.get(e.name, 0.0) + part
    return [[k, v / 1e9] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def top_gaps(r: Reduced, n: int = 10) -> list:
    """Longest idle pieces of the traced window, each named by what the host
    was doing: inside a job of a service, or waiting between jobs.  A gap
    that spans a job's edge is cut there."""
    out = []
    for s, e in gaps(r.busy, *r.window):
        t = s
        for svc, js, je in r.jobs:
            if je <= t or js >= e:
                continue
            if js > t:
                out.append(["between jobs", (js - t) / 1e9])
            end = min(je, e)
            out.append([f"in job:{svc}", (end - max(js, t)) / 1e9])
            t = end
        if t < e:
            out.append(["between jobs", (e - t) / 1e9])
    return sorted(out, key=lambda g: -g[1])[:n]
