"""R and wait arithmetic from a hand-built EventTrace."""
import pytest

import jobs
import registry
from repro.sched import EventTrace


def _trace():
    tr = EventTrace(us_per_unit=1e6)
    # service a, period 0.1: released 0.0, starts 0.001, done 0.031
    tr.record(0.0, "release", "a")
    tr.record(0.0, "release", "b")
    tr.record(0.001, "start", "a")
    tr.record(0.031, "complete", "a", response_s=0.031)
    # b waited behind a: released 0.0, starts 0.031, done 0.081
    tr.record(0.031, "start", "b")
    tr.record(0.081, "complete", "b", response_s=0.081)
    tr.record(0.1, "release", "a")
    tr.record(0.1005, "start", "a")
    tr.record(0.1305, "complete", "a", response_s=0.0305)
    return tr


def test_jobs_from_events():
    got = jobs.from_events(_trace().events)
    assert [j.service for j in got] == ["a", "b", "a"]
    a0, b0, a1 = got
    assert a0.release == pytest.approx(0.0)
    assert b0.response == pytest.approx(0.081)
    assert b0.wait == pytest.approx(0.031)
    assert a1.release == pytest.approx(0.1)
    assert a1.wait == pytest.approx(0.0005)
    assert a1.complete - a1.start == pytest.approx(0.03)


def test_executor_wait_reader():
    record = {"jobs": jobs.from_events(_trace().events)}
    waits_ms = [1.0, 31.0, 0.5]
    assert registry.metric("executor_wait_ms_p95").read(record) == \
        pytest.approx(jobs.percentile(waits_ms, 95))
    assert registry.metric("executor_wait_ms_p95").read({"jobs": []}) is None


def test_percentile_and_counts():
    assert jobs.percentile([1, 2, 3, 4, 5], 50) == 3
    assert jobs.percentile(list(range(101)), 95) == pytest.approx(95)
    with pytest.raises(ValueError):
        jobs.percentile([], 50)
    assert jobs.releases(0.1, 1.0) == 10
    assert jobs.releases(0.3, 1.0) == 4
    # 10 released, 7 completed: releases 7, 8, 9 at 0.7, 0.8, 0.9 s with a
    # 0.25 s deadline; only 0.7 + 0.25 <= 1.0 is due inside the window
    assert jobs.overdue_unfinished(10, 7, 0.1, 0.25, 1.0) == 1
    assert jobs.overdue_unfinished(10, 10, 0.1, 0.25, 1.0) == 0
