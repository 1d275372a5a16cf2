"""Online-scheduler tests: dynamic admission, mode-change protocol,
churn-trace validation, and event telemetry.

The load-bearing property (ISSUE acceptance): across an entire sporadic
admit/release churn trace, every job of every admitted task observes
R ≤ the analytic R̂ certified by its admission epoch — zero misses, zero
bound violations — while slices move between services only at job
boundaries.
"""
import json

import numpy as np
import pytest

from repro.core import (
    ChurnConfig,
    GeneratorConfig,
    generate_churn_trace,
    generate_taskset,
)
from repro.core.federated import grid_search_dfs
from repro.core.rta import AnalysisTables
from repro.runtime import AdmissionController, simulate, simulate_churn
from repro.sched import DynamicController, EventTrace


def _tasks(seed=0, util=0.5, n=6, m=3):
    rng = np.random.default_rng(seed)
    return list(generate_taskset(rng, util, GeneratorConfig(n_tasks=n, n_subtasks=m)))


class TestChurnTraceValidation:
    def test_fifty_event_trace_no_miss_no_bound_violation(self):
        """≥50 admit/release events; observed R ≤ analytic R̂ for every job."""
        events = generate_churn_trace(seed=0, horizon=10_000.0,
                                      config=ChurnConfig())
        assert len(events) >= 50
        trace = EventTrace()
        res = simulate_churn(events, gn_total=10, horizon=11_000.0, seed=0,
                             trace=trace)
        assert len(res.admitted) >= 10
        assert res.total_jobs >= 50
        assert not res.any_miss, f"misses: {res.misses}"
        assert res.bound_violations() == []
        # every admitted service actually ran and was traced
        counts = trace.counts()
        assert counts["release"] == counts["complete"] == res.total_jobs
        assert counts["admit"] == len(res.admitted)

    def test_worst_case_trace_still_sound(self):
        events = generate_churn_trace(seed=3, horizon=8_000.0,
                                      config=ChurnConfig())
        res = simulate_churn(events, gn_total=8, horizon=9_000.0, seed=3,
                             worst_case=True, release_jitter=False)
        assert not res.any_miss
        assert res.bound_violations() == []

    def test_pinned_only_controller_also_sound(self):
        events = generate_churn_trace(seed=1, horizon=8_000.0,
                                      config=ChurnConfig())
        res = simulate_churn(events, gn_total=10, horizon=9_000.0, seed=1,
                             allow_realloc=False)
        assert not res.any_miss
        assert res.bound_violations() == []


class TestRejectionPath:
    def test_rejected_admit_leaves_state_byte_identical(self):
        """Alloc map, bounds, analysis cache, epoch: all unchanged."""
        tasks = _tasks(seed=0)
        c = DynamicController(gn_total=6)
        for t in tasks[:3]:
            assert c.admit(t).admitted
        bad = _tasks(seed=7, util=40.0, n=1)[0]
        fp = c.fingerprint()
        alloc = c.allocation
        dec = c.admit(bad)
        assert not dec.admitted and dec.reason
        assert c.fingerprint() == fp
        assert c.allocation == alloc

    def test_rejected_admit_is_deterministic(self):
        tasks = _tasks(seed=0)
        c = DynamicController(gn_total=6)
        for t in tasks[:3]:
            c.admit(t)
        bad = _tasks(seed=7, util=40.0, n=1)[0]
        d1 = c.admit(bad)
        d2 = c.admit(bad)
        assert (d1.admitted, d1.reason, d1.tried, d1.path) == \
               (d2.admitted, d2.reason, d2.tried, d2.path)

    def test_rejected_update_rate_keeps_rate(self):
        tasks = _tasks(seed=2, util=0.8)
        c = DynamicController(gn_total=4)
        admitted = [t for t in tasks if c.admit(t).admitted]
        assert admitted
        name = admitted[0].name
        before = c.fingerprint()
        dec = c.update_rate(name, period=0.5, deadline=0.4)
        assert not dec.admitted
        assert c.fingerprint() == before
        assert c.task(name).period == admitted[0].period


class TestModeChangeProtocol:
    def test_slices_reclaimed_only_at_job_boundary(self):
        tasks = _tasks(seed=4, util=0.4, n=3)
        c = DynamicController(gn_total=4)
        for t in tasks:
            assert c.admit(t).admitted
        victim = c.order()[-1]
        used = c.capacity_in_use
        assert c.release(victim)
        # departing: still analyzed, slices still held
        assert c.is_departing(victim)
        assert c.capacity_in_use == used
        assert c.job_boundary(victim) == "reclaimed"
        assert victim not in c.allocation
        assert c.capacity_in_use < used

    def test_arrival_waits_for_reclamation(self):
        """A task needing the departer's slices is rejected while the
        departer is in flight, admitted after its job boundary."""
        import dataclasses

        rng = np.random.default_rng(5)
        big = generate_taskset(rng, 0.5, GeneratorConfig(n_tasks=1))[0]
        c = DynamicController(gn_total=2, allow_realloc=False)
        assert c.admit(big).admitted
        gn_big = c.allocation[big.name]
        rival = dataclasses.replace(big, name="rival")
        c.release(big.name)
        d1 = c.admit(rival)           # departer still holds its slices
        if d1.admitted:               # only possible if capacity allowed both
            assert c.capacity_in_use <= c.gn_total
            return
        assert "capacity" in d1.reason or "unschedulable" in d1.reason
        c.job_boundary(big.name)      # reclaim
        d2 = c.admit(rival)
        assert d2.admitted
        assert c.allocation == {"rival": gn_big}

    def test_update_rate_staged_until_boundary(self):
        tasks = _tasks(seed=8, util=0.3, n=2)
        c = DynamicController(gn_total=6)
        for t in tasks:
            assert c.admit(t).admitted
        name = c.order()[0]
        old = c.task(name)
        dec = c.update_rate(name, period=old.period * 2,
                            deadline=old.deadline * 1.5)
        assert dec.admitted and dec.path == "update"
        # committed params unchanged until the job boundary
        assert c.task(name).period == old.period
        assert c.job_boundary(name) == "committed"
        assert c.task(name).period == old.period * 2

    def test_instant_mode_commits_immediately(self):
        tasks = _tasks(seed=8, util=0.3, n=2)
        c = DynamicController(gn_total=6, transition="instant")
        for t in tasks:
            assert c.admit(t).admitted
        name = c.order()[0]
        old = c.task(name)
        assert c.update_rate(name, old.period * 2, old.deadline).admitted
        assert c.task(name).period == old.period * 2
        assert c.release(name)
        assert name not in c.allocation


class TestWarmStart:
    def test_hint_revalidates_previous_allocation(self):
        rng = np.random.default_rng(11)
        ts = generate_taskset(rng, 0.6, GeneratorConfig(n_tasks=6))
        tables = AnalysisTables()
        cold = grid_search_dfs(ts, 12, tightened=True, tables=tables)
        if not cold.schedulable:
            pytest.skip("unschedulable draw")
        warm = grid_search_dfs(ts, 12, tightened=True, hint=cold.alloc,
                               tables=tables)
        assert warm.alloc == cold.alloc
        assert warm.candidates_tried <= cold.candidates_tried

    def test_tables_shared_across_admissions(self):
        tasks = _tasks(seed=0)
        c = DynamicController(gn_total=10)
        sizes = []
        for t in tasks:
            if c.admit(t).admitted:
                sizes.append(len(c._tables))
        assert sizes == sorted(sizes)        # cache only grows
        assert sizes[-1] > 0

    def test_pinned_path_is_narrow(self):
        """The warm pinned path sizes only the arrival: candidate vectors
        tried are bounded by free capacity, not the full grid."""
        tasks = _tasks(seed=0, n=6)
        c = DynamicController(gn_total=10)
        for t in tasks:
            dec = c.admit(t)
            if dec.admitted and dec.path == "pinned":
                assert dec.tried <= c.gn_total


    def test_warm_admission_decides_as_cold_grid_search(self):
        """Each arrival of a churn trace decided twice: by the warm
        controller and by Algorithm 2 run cold on the same candidate set.
        Where neither search hits its budget the two agree; the trace
        reaches six residents; run end to end it misses no deadline and
        violates no bound."""
        from repro.core import TaskSet, analyze_rtgpu_plus, schedule

        budget = 400
        events = generate_churn_trace(
            seed=0, horizon=6000.0,
            config=ChurnConfig(mean_interarrival=250.0,
                               lifetime_range=(2500.0, 5000.0),
                               util_range=(0.05, 0.12),
                               task_config=GeneratorConfig(n_subtasks=3)),
        )
        warm = DynamicController(10, transition="instant",
                                 max_candidates=budget)
        compared = largest = 0
        for ev in events:
            if ev.kind == "release":
                warm.release(ev.name)
                continue
            cand = TaskSet.deadline_monotonic(
                [warm.task(n) for n in warm.order()] + [ev.task])
            cold = schedule(cand, 10, analyzer=analyze_rtgpu_plus,
                            mode="grid", max_candidates=budget)
            dec = warm.admit(ev.task)
            if dec.admitted:
                largest = max(largest, len(cand))
            if cold.candidates_tried < budget and dec.tried < budget:
                assert dec.admitted == cold.schedulable, ev.name
                compared += 1
        assert compared > 0
        assert largest >= 6
        res = simulate_churn(events, 10, 7000.0, seed=0)
        assert not res.any_miss, res.misses
        assert res.bound_violations() == []


class TestTelemetry:
    def test_simulator_trace_records_and_exports(self):
        from repro.core import analyze_rtgpu_plus, schedule

        rng = np.random.default_rng(1)
        ts = generate_taskset(rng, 0.5, GeneratorConfig())
        res = schedule(ts, 10, analyzer=analyze_rtgpu_plus, mode="greedy+grid")
        assert res.schedulable
        trace = EventTrace()
        sim = simulate(ts, list(res.alloc), 10 * max(t.period for t in ts),
                       seed=1, trace=trace)
        counts = trace.counts()
        assert counts["release"] >= counts.get("complete", 0) > 0
        assert counts.get("complete", 0) == sum(sim.jobs)
        assert not trace.misses()

    def test_chrome_export_structure(self, tmp_path):
        trace = EventTrace(us_per_unit=1000.0, label="test")
        trace.record(0.0, "admit", "a", gn=2)
        trace.record(1.0, "release", "a", deadline=11.0)
        trace.record(5.0, "complete", "a", response=4.0)
        trace.record(6.0, "miss", "b", overshoot=0.5)
        doc = trace.to_chrome()
        evs = doc["traceEvents"]
        begins = [e for e in evs if e.get("ph") == "B"]
        ends = [e for e in evs if e.get("ph") == "E"]
        assert len(begins) == len(ends) == 1
        assert begins[0]["ts"] == 1000.0           # ms -> us
        names = {e["args"]["name"] for e in evs if e["name"] == "thread_name"}
        assert names == {"a", "b"}
        path = trace.dump(str(tmp_path / "trace.json"))
        with open(path) as fh:
            assert json.load(fh)["traceEvents"]

    def test_json_round_trip_is_stable(self, tmp_path):
        """dump → load → dump byte-stability, plus verbatim event recovery
        (including nested-dict meta like the controller's realloc target)."""
        trace = EventTrace(us_per_unit=1000.0, label="rt")
        trace.record(0.0, "admit", "a", gn=3, path="pinned", bound=12.5)
        trace.record(0.25, "release", "a", deadline=10.25)
        trace.record(1.0, "realloc", "a", target={"a": 3, "b": 1})
        trace.record(4.75, "complete", "a", response=4.5)
        trace.record(5.0, "miss", "b", overshoot=0.125)
        first = trace.dumps()
        loaded = EventTrace.loads(first)
        assert loaded.dumps() == first
        assert loaded.events == trace.events
        assert loaded.us_per_unit == trace.us_per_unit
        assert loaded.label == trace.label
        assert trace.diff(loaded) is None
        # file round-trip too
        path = trace.save(str(tmp_path / "events.json"))
        again = EventTrace.load(path)
        assert again.dumps() == first

    def test_diff_reports_first_divergence(self):
        a = EventTrace()
        b = EventTrace()
        a.record(0.0, "release", "x", deadline=5.0)
        b.record(0.0, "release", "x", deadline=5.0)
        a.record(1.0, "complete", "x", response=1.0)
        b.record(1.0, "complete", "x", response=2.0)
        idx, ours, theirs = a.diff(b)
        assert idx == 1
        assert ours.meta != theirs.meta
        b.events[1] = a.events[1]
        b.record(2.0, "release", "x", deadline=7.0)
        idx, ours, theirs = a.diff(b)
        assert idx == 2 and ours is None and theirs is not None

    def test_controller_events_traced(self):
        trace = EventTrace()
        c = DynamicController(gn_total=6, trace=trace)
        tasks = _tasks(seed=0, n=3)
        for t in tasks:
            c.admit(t)
        c.release(tasks[0].name)
        c.job_boundary(tasks[0].name)
        counts = trace.counts()
        assert counts.get("admit", 0) >= 1
        assert counts.get("depart") == 1 and counts.get("reclaim") == 1


class TestAdmissionWrapper:
    def test_wrapper_delegates_to_dynamic_controller(self):
        ac = AdmissionController(gn_total=8)
        assert isinstance(ac.dynamic, DynamicController)
        tasks = _tasks(seed=0, n=4, util=0.4)
        for t in tasks:
            ac.admit(t)
        assert sum(ac.allocation.values()) <= 8
        assert ac.dynamic.transition == "instant"
        name = next(iter(ac.allocation))
        assert ac.remove(name)
        assert name not in ac.allocation
        assert not ac.remove(name)

    def test_wrapper_readmission_after_removal(self):
        ac = AdmissionController(gn_total=8)
        t = _tasks(seed=0, n=1, util=0.2)[0]
        assert ac.admit(t).admitted
        assert ac.remove(t.name)
        assert ac.admit(t).admitted


class TestServingRegistration:
    def test_engine_registers_and_deregisters(self):
        from repro.configs import get_smoke_config
        from repro.runtime import ServingTaskSpec
        from repro.serving import ServeConfig, ServingEngine

        cfg = get_smoke_config("qwen3-0.6b")
        eng = ServingEngine(cfg, ServeConfig(max_context=64, batch=2))
        c = DynamicController(gn_total=8)
        spec = ServingTaskSpec(
            name="svc", arch_id="qwen3-0.6b", period_ms=50.0,
            deadline_ms=40.0, batch=2, seq_len=64, new_tokens=2,
            roofline_step_s=0.002, collective_s=2e-4, dominant="compute_s",
        )
        dec = eng.rt_register(c, spec)
        assert dec.admitted and eng.rt_registered
        assert "svc" in c.allocation
        assert eng.rt_deregister()          # departs via mode-change protocol
        assert c.is_departing("svc")
        assert c.job_boundary("svc") == "reclaimed"
        assert "svc" not in c.allocation
        assert not eng.rt_deregister()
