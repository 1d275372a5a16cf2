"""Jobs of a window, read from the executor's ``EventTrace``.

The executor is single-threaded and non-preemptive: each ``start`` of a
service is followed by that job's ``complete``.  ``complete`` carries
``response_s``, measured from the job's *due* release, so

  release  = complete - response_s
  run      = complete - start          (the job itself)
  wait     = response_s - run          (queued behind other jobs, or the
                                        executor's polling delay)
"""
from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np


class Job(NamedTuple):
    service: str
    release: float
    start: float
    complete: float

    @property
    def response(self) -> float:
        return self.complete - self.release

    @property
    def wait(self) -> float:
        return self.start - self.release


def from_events(events) -> list[Job]:
    started: dict[str, float] = {}
    out = []
    for ev in events:
        if ev.kind == "start":
            started[ev.task] = ev.t
        elif ev.kind == "complete":
            resp = dict(ev.meta)["response_s"]
            out.append(Job(ev.task, ev.t - resp, started.pop(ev.task), ev.t))
    return out


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    if not len(values):
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def overdue_unfinished(released: int, completed: int, period_s: float,
                       deadline_s: float, window_s: float) -> int:
    """Jobs of one service that were released but never completed, and whose
    deadline passed inside the window.  Releases are at k * period; the
    executor runs a service's jobs in release order, so the unfinished ones
    are the last ``released - completed``."""
    return sum(1 for k in range(completed, released)
               if k * period_s + deadline_s <= window_s)


def releases(period_s: float, window_s: float) -> int:
    """Jobs a periodic service releases in a window (at 0, P, 2P, ...)."""
    return math.ceil(window_s / period_s)
