"""Preemptive GPU arbitration: analysis, certification, and engine seam.

The arbitration model (``PreemptionModel``) is one pluggable seam from
analysis to engine:

  * ``preemption="none"`` must be a *pure refactor* — the engine replays
    every recorded golden byte-exactly, and the analysis is unchanged;
  * under ``preemption="priority"`` the scalar analysis and the batched
    lockstep twin must stay bit-identical (decisions AND bounds), the
    certified R̂ must never be optimistic against the priority-preemptive
    engine (hypothesis property over churn containing real preemptions),
    and the engine must charge the context-switch overhead exactly as the
    analysis models it.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core import (
    ChurnConfig,
    GeneratorConfig,
    PreemptionModel,
    SegmentKind,
    TaskSet,
    generate_churn_trace,
    generate_taskset,
    golden_scenario,
)
from repro.core.rta import RtgpuIncremental, gpu_blocking
from repro.core.rta_batch import BatchAnalyzer
from repro.runtime import simulate_churn
from repro.runtime.engine import DiscreteEventEngine, EngineJob, SchedulingPolicy
from repro.sched import (
    BatchCertifier,
    DynamicController,
    EventTrace,
    PreemptiveCertifier,
    ScalarCertifier,
    make_certifier,
)

#: churn regime where slice capacity (not the GPU) is the binding
#: constraint — the same regime the `preemptive_churn` golden and
#: `TestPreemptiveCertification::test_preset_stream_preempts_and_holds_bounds`
#: exercise (one source of truth)
PRESET = golden_scenario("preemptive_churn")
CAPACITY_BOUND = PRESET.churn


def _gpu_preempts(trace: EventTrace) -> int:
    return sum(
        1 for ev in trace.events
        if ev.kind == "preempt" and dict(ev.meta).get("resource") == "gpu"
    )


# ---- analysis layer ---------------------------------------------------------


class TestPreemptiveAnalysis:
    def test_none_mode_is_identical_to_default(self):
        ts = generate_taskset(np.random.default_rng(0), 0.5,
                              GeneratorConfig(n_tasks=4, n_subtasks=3))
        alloc = [2, 2, 2, 2]
        a = RtgpuIncremental(ts)
        b = RtgpuIncremental(ts, preemption="none")
        c = RtgpuIncremental(ts, preemption=PreemptionModel())
        for k in range(len(ts)):
            ref = a.analyze_task(k, alloc)
            assert ref == b.analyze_task(k, alloc)
            assert ref == c.analyze_task(k, alloc)

    def test_priority_never_below_dedicated(self):
        """Serializing the GPU can only add delay: for any task and
        allocation, the preemptive R̂ dominates the dedicated one."""
        ts = generate_taskset(np.random.default_rng(3), 0.6,
                              GeneratorConfig(n_tasks=5, n_subtasks=4))
        alloc = [2] * len(ts)
        ded = RtgpuIncremental(ts)
        pre = RtgpuIncremental(ts, preemption=PreemptionModel("priority", 0.05))
        for k in range(len(ts)):
            r_ded = ded.analyze_task(k, alloc).response
            r_pre = pre.analyze_task(k, alloc).response
            assert r_pre >= r_ded - 1e-12

    def test_highest_priority_task_pays_only_blocking(self):
        """Task 0 sees no higher-priority GPU interference — its kernel
        bound is the dedicated one plus exactly the lower-priority
        blocking term (one context switch)."""
        ts = generate_taskset(np.random.default_rng(7), 0.4,
                              GeneratorConfig(n_tasks=3, n_subtasks=3))
        ctx = 0.25
        pre = RtgpuIncremental(ts, preemption=PreemptionModel("priority", ctx))
        ded = RtgpuIncremental(ts)
        ta_p = pre.analyze_task(0, [2])
        ta_d = ded.analyze_task(0, [2])
        for hp, hd in zip(ta_p.gpu_resp_hi, ta_d.gpu_resp_hi):
            assert hp == pytest.approx(hd + ctx, abs=1e-9)

    def test_gpu_blocking_suffix(self):
        ts = generate_taskset(np.random.default_rng(1), 0.5,
                              GeneratorConfig(n_tasks=4, n_subtasks=3))
        blk = gpu_blocking(ts.tasks, 0.5)
        # every task here has kernels, so all but the last are blocked
        assert blk == [0.5, 0.5, 0.5, 0.0]
        single_cpu = dataclasses.replace(
            ts.tasks[-1], cpu_lo=(1.0,), cpu_hi=(2.0,), mem_lo=(), mem_hi=(),
            gpu=(),
        )
        blk2 = gpu_blocking(list(ts.tasks[:2]) + [single_cpu], 0.5)
        # the kernel-free lowest-priority task blocks nobody
        assert blk2 == [0.5, 0.0, 0.0]

    def test_scalar_vs_batched_bit_identical(self):
        import itertools

        ts = generate_taskset(
            np.random.default_rng(11), 0.6,
            GeneratorConfig(n_tasks=4, n_subtasks=4, variability=0.2),
        )
        pm = PreemptionModel("priority", 0.05)
        for tight in (False, True):
            inc = RtgpuIncremental(ts, tightened=tight, preemption=pm)
            ana = BatchAnalyzer(ts, tightened=tight, preemption=pm)
            for alloc in itertools.product((1, 2, 3), repeat=len(ts)):
                for k in range(len(ts)):
                    sa = inc.analyze_task(k, alloc[: k + 1])
                    da = ana.analyze_prefixes(
                        k, np.asarray([alloc[: k + 1]]), dedupe=False
                    )
                    ba = da.task_analysis(0)
                    assert sa.r1 == ba.r1 and sa.r2 == ba.r2, (alloc, k)
                    assert sa.gpu_resp_hi == ba.gpu_resp_hi, (alloc, k)

    def test_fused_pinned_rows_bit_identical(self):
        """The fused pinned-sweep matrix (``analyze_pinned``: every task at
        or below the pinned position × every candidate GN in two engine
        calls) reproduces the scalar oracle's R̂ bit-for-bit — including
        mid-transition shapes where interference and own-GN vectors
        differ."""
        ts = generate_taskset(
            np.random.default_rng(11), 0.6,
            GeneratorConfig(n_tasks=5, n_subtasks=4, variability=0.2),
        )
        gs = [1, 2, 3, 4]
        for pm in (PreemptionModel("priority", 0.05), PreemptionModel()):
            for tight in (False, True):
                ana = BatchAnalyzer(ts, tightened=tight, preemption=pm)
                rng = np.random.default_rng(7)
                for _ in range(10):
                    interf = [int(g) for g in rng.integers(1, 5, len(ts))]
                    own = [int(g) for g in rng.integers(1, 5, len(ts))]
                    a = int(rng.integers(0, len(ts)))
                    out = ana.analyze_pinned(a, interf, own, gs)
                    for c, g in enumerate(gs):
                        iv = list(interf)
                        sv = list(own)
                        iv[a] = sv[a] = g
                        for k in range(a, len(ts)):
                            ref = ana.scalar.analyze_task(
                                k, iv[:k] + [sv[k]]
                            ).response
                            got = out[c, k - a]
                            assert got == ref or (
                                math.isinf(got) and math.isinf(ref)
                            ), (pm.mode, tight, a, g, k)


# ---- certification layer ----------------------------------------------------


class TestPreemptiveCertification:
    def _tasks(self, seed: int, n: int = 10):
        rng = np.random.default_rng(seed)
        out = []
        for i in range(n):
            t = generate_taskset(
                rng, float(rng.uniform(0.04, 0.1)),
                GeneratorConfig(n_tasks=1, n_subtasks=3),
            )[0]
            out.append(dataclasses.replace(t, name=f"svc{i}"))
        return out

    def test_make_certifier_resolves_preemptive(self):
        assert isinstance(make_certifier("preemptive", gpu_ctx=0.1),
                          PreemptiveCertifier)
        c = make_certifier("batch", preemption="priority", gpu_ctx=0.1)
        assert isinstance(c, PreemptiveCertifier)
        assert c.preemption == PreemptionModel("priority", 0.1)
        s = make_certifier("scalar", preemption="priority", gpu_ctx=0.1)
        assert isinstance(s, ScalarCertifier)
        assert s.preemption.enabled
        assert isinstance(make_certifier("batch"), BatchCertifier)
        assert not make_certifier("batch").preemption.enabled

    def test_scalar_and_batch_controllers_decide_identically(self):
        """Preemptive certification is engine-independent: the scalar and
        batched controllers admit the same services at the same GN with
        the same certified bounds."""
        tasks = self._tasks(5, n=12)
        kw = dict(transition="boundary", preemption="priority",
                  gpu_ctx_overhead=0.05)
        cs = DynamicController(4, engine="scalar", **kw)
        cb = DynamicController(4, engine="batch", **kw)
        any_admitted = False
        for t in tasks:
            ds, db = cs.admit(t), cb.admit(t)
            assert ds.admitted == db.admitted, t.name
            assert ds.alloc == db.alloc
            assert ds.bounds == db.bounds
            any_admitted |= ds.admitted
        assert any_admitted
        assert cs.allocation == cb.allocation
        assert cs.bounds() == cb.bounds()

    def test_overlapping_holdings_certified(self):
        """Priority arbitration drops the capacity-disjointness constraint:
        total holdings may exceed the pool once certified."""
        tasks = self._tasks(2, n=14)
        ctl = DynamicController(3, preemption="priority",
                                gpu_ctx_overhead=0.02)
        for t in tasks:
            ctl.admit(t)
        assert ctl.capacity_in_use > ctl.gn_total
        assert ctl.free_capacity < 0
        assert all(g <= ctl.gn_total for g in ctl.allocation.values())

    def test_admission_gain_over_dedication(self):
        """≥1 service admitted under priority arbitration that federated
        dedication rejects, on the same arrival stream."""
        events = generate_churn_trace(seed=2, horizon=4000.0,
                                      config=CAPACITY_BOUND)
        rn = simulate_churn(events, gn_total=4, horizon=5000.0, seed=2)
        rp = simulate_churn(events, gn_total=4, horizon=5000.0, seed=2,
                            preemption="priority", gpu_ctx_overhead=0.02)
        extra = set(rp.admitted) - set(rn.admitted)
        assert extra, "priority arbitration admitted nothing new"
        assert len(rp.admitted) > len(rn.admitted)
        assert not rp.any_miss
        assert rp.bound_violations() == []

    def test_preset_stream_admits_more_than_dedication(self):
        """The `preemptive_churn` arrival stream offered to a dedicated
        and a preemptive controller: the preemptive one accepts more."""
        accepted = {}
        for preemption in ("none", "priority"):
            ctl = DynamicController(
                PRESET.gn_total, transition="instant",
                preemption=preemption,
                gpu_ctx_overhead=PRESET.gpu_ctx_overhead,
            )
            accepted[preemption] = 0
            for ev in generate_churn_trace(seed=PRESET.seed,
                                           horizon=PRESET.churn_horizon,
                                           config=CAPACITY_BOUND):
                if ev.kind == "release":
                    ctl.release(ev.name)
                else:
                    accepted[preemption] += ctl.admit(ev.task,
                                                      t=ev.time).admitted
        assert accepted["priority"] > accepted["none"] > 0

    def test_preset_stream_preempts_and_holds_bounds(self):
        """The same stream run end to end: a service admitted that
        dedication rejects, at least one GPU preemption, no miss, no
        bound violation, and a `BoundMonitor` that gauges every resident
        and raises no `bound_violation` alert."""
        from repro.obs import BoundMonitor

        events = generate_churn_trace(seed=PRESET.seed,
                                      horizon=PRESET.churn_horizon,
                                      config=CAPACITY_BOUND)
        rn = simulate_churn(events, PRESET.gn_total, horizon=PRESET.horizon,
                            seed=PRESET.seed)
        trace = EventTrace()
        monitor = BoundMonitor()
        rp = simulate_churn(events, PRESET.gn_total, horizon=PRESET.horizon,
                            seed=PRESET.seed, preemption="priority",
                            gpu_ctx_overhead=PRESET.gpu_ctx_overhead,
                            trace=trace, monitor=monitor)
        assert set(rp.admitted) - set(rn.admitted)
        assert _gpu_preempts(trace) > 0
        assert not rp.any_miss, rp.misses
        assert rp.bound_violations() == []
        assert not set(rp.admitted) - set(monitor.gauges())
        assert not any(a.kind == "bound_violation" for a in monitor.alerts)

    def test_rejection_is_transactional_under_preemption(self):
        tasks = self._tasks(9, n=16)
        ctl = DynamicController(2, preemption="priority",
                                gpu_ctx_overhead=0.05)
        rejected = None
        for t in tasks:
            if not ctl.admit(t).admitted:
                rejected = t
                break
        assert rejected is not None, "pool too large: nothing was rejected"
        fp = ctl.fingerprint()
        again = ctl.admit(rejected)
        assert not again.admitted
        assert ctl.fingerprint() == fp

    def test_engine_name_preemptive_sets_model_coherently(self):
        """engine="preemptive" alone must flip the whole seam: the model
        the capacity rule and the runtime read agrees with the certifier
        (and carries the ctx overhead)."""
        ctl = DynamicController(4, engine="preemptive",
                                gpu_ctx_overhead=0.05)
        assert ctl.preemption == PreemptionModel("priority", 0.05)
        assert ctl._certifier.preemption == ctl.preemption

    def test_instant_mode_realloc_unblocks_arrivals(self):
        """Preemptive re-allocation (per-task coordinate descent over
        overlapping slices) admits arrivals the pinned sweep rejects, by
        re-sizing residents' GNs — and never admits less than the
        pinned-only controller."""
        tasks = self._tasks(4, n=12)
        kw = dict(transition="instant", preemption="priority",
                  gpu_ctx_overhead=0.05)
        ctl = DynamicController(2, **kw)
        ctl_no = DynamicController(2, allow_realloc=False, **kw)
        paths = []
        for t in tasks:
            dec = ctl.admit(t)
            paths.append(dec.path)
            if dec.admitted:
                assert set(dec.bounds) == set(ctl.allocation)
                assert all(math.isfinite(b) for b in dec.bounds.values())
            ctl_no.admit(t)
        assert "realloc" in paths
        assert set(ctl_no.allocation) < set(ctl.allocation)
        assert all(1 <= g <= ctl.gn_total for g in ctl.allocation.values())

    def test_scalar_engine_still_skips_realloc_under_preemption(self):
        """The scalar DFS enumerates a dedicated sum budget, which doesn't
        model time-shared slices — under preemption that engine must keep
        skipping the re-allocation fallback."""
        tasks = self._tasks(4, n=12)
        ctl = DynamicController(2, engine="scalar", transition="instant",
                                preemption="priority", gpu_ctx_overhead=0.05)
        assert not ctl._certifier.supports_preemptive_realloc
        for t in tasks:
            dec = ctl.admit(t)
            assert dec.path in ("pinned", "")   # never "realloc"

    def test_batch_sweep_warms_shared_certify_memo(self):
        """Bounds certified by the batched sweeps land in the shared memo
        under the scalar loop's keys: re-certifying the freshly admitted
        set costs zero new analyses (and zero memo misses)."""
        from repro.obs import metrics

        for engine, preemption in (("batch", "priority"), ("batch", None)):
            ctl = DynamicController(
                4, engine=engine, preemption=preemption,
                gpu_ctx_overhead=0.05,
            )
            ctl._certifier.min_work = 1   # force the batched path
            admitted = [t for t in self._tasks(3, n=8)
                        if ctl.admit(t).admitted]
            assert len(admitted) >= 2
            reg = metrics.registry()
            misses0 = reg.value("certify_memo_misses_total") or 0.0
            bounds, analyses, reason = ctl._certifier.certify(
                ctl._pool.entries(), ctl._tables.fork(), dict(ctl._memo)
            )
            assert reason == "" and bounds is not None
            assert analyses == 0
            misses1 = reg.value("certify_memo_misses_total") or 0.0
            assert misses1 == misses0
            assert bounds == ctl.bounds()


# ---- engine seam ------------------------------------------------------------


class _TwoKernelPolicy(SchedulingPolicy):
    """Two single-segment GPU jobs with controlled release times: ``lo``
    (low priority) at t=0 for 10 time units, ``hi`` at t=3 for 2."""

    RELEASES = {"lo": 0.0, "hi": 3.0}
    LENGTHS = {"lo": 10.0, "hi": 2.0}

    def __init__(self):
        self.done: dict[str, float] = {}

    def bind(self, engine):
        super().bind(engine)
        engine.jobs = {"hi": None, "lo": None}
        self.pending = dict(self.RELEASES)

    def release_jobs(self, now):
        for name, t in list(self.pending.items()):
            if t <= now + 1e-9:
                del self.pending[name]
                self.engine.start_job(name, EngineJob(
                    release=t, deadline_abs=t + 100.0,
                    chain=[(SegmentKind.GPU, 0)],
                    durations=[self.LENGTHS[name]],
                ))

    def arbitration_order(self):
        return ["hi", "lo"]

    def next_external_time(self, now):
        return min(self.pending.values(), default=math.inf)

    def on_job_complete(self, key, job, now, response):
        self.done[key] = now
        self.engine.jobs[key] = None


class _PriorityTwoKernelPolicy(_TwoKernelPolicy):
    CTX = 0.5

    def gpu_arbitration(self):
        return ("priority", self.CTX)


class TestEngineArbitration:
    def test_dedicated_lanes_run_concurrently(self):
        policy = _TwoKernelPolicy()
        trace = EventTrace()
        DiscreteEventEngine(policy, trace=trace).run(50.0)
        assert policy.done == {"hi": 5.0, "lo": 10.0}
        assert _gpu_preempts(trace) == 0

    def test_priority_preempts_and_charges_ctx(self):
        """hi arrives at t=3 mid-kernel: lo is evicted (one preempt event,
        +ctx to its remaining), hi runs 3→5, lo resumes and finishes at
        exactly 10 + 2 (hi occupancy) + 0.5 (context switch)."""
        policy = _PriorityTwoKernelPolicy()
        trace = EventTrace()
        DiscreteEventEngine(policy, trace=trace).run(50.0)
        assert policy.done["hi"] == pytest.approx(5.0)
        assert policy.done["lo"] == pytest.approx(12.5)
        pre = [ev for ev in trace.events if ev.kind == "preempt"]
        res = [ev for ev in trace.events if ev.kind == "resume"]
        assert len(pre) == 1 and len(res) == 1
        assert pre[0].task == "lo" and dict(pre[0].meta)["by"] == "hi"
        assert dict(pre[0].meta)["resource"] == "gpu"
        assert pre[0].t == pytest.approx(3.0)
        assert res[0].task == "lo" and res[0].t == pytest.approx(5.0)

    def test_no_phantom_preempt_across_job_boundary(self):
        """A successor job whose chain opens with a kernel must not be
        billed for its predecessor's completed one: ownership is released
        with the kernel, so a hand-off exactly at the boundary is a fresh
        acquisition, not an eviction."""

        class _BackToBack(_TwoKernelPolicy):
            # lo's first kernel ends at t=5, its second starts right
            # there; hi arrives at that same instant and wins the context
            RELEASES = {"lo": 0.0, "hi": 5.0}
            LENGTHS = {"lo": 5.0, "hi": 2.0}

            def __init__(self):
                super().__init__()
                self.lo_jobs = 0

            def gpu_arbitration(self):
                return ("priority", 0.5)

            def on_job_complete(self, key, job, now, response):
                super().on_job_complete(key, job, now, response)
                if key == "lo":
                    self.lo_jobs += 1
                    if self.lo_jobs == 1:
                        self.pending[key] = now   # back-to-back release

        policy = _BackToBack()
        trace = EventTrace()
        DiscreteEventEngine(policy, trace=trace).run(50.0)
        # hi runs 5→7, lo's second kernel 7→12 — no preempt, no ctx charge
        assert policy.done["hi"] == pytest.approx(7.0)
        assert policy.done["lo"] == pytest.approx(12.0)
        assert _gpu_preempts(trace) == 0
        assert not [ev for ev in trace.events if ev.kind == "resume"]

    def test_unknown_mode_rejected(self):
        policy = _TwoKernelPolicy()
        policy.gpu_arbitration = lambda: ("fifo", 0.0)
        with pytest.raises(ValueError, match="fifo"):
            DiscreteEventEngine(policy).run(1.0)


# ---- golden equivalence -----------------------------------------------------


@pytest.mark.parametrize("name", ["steady", "churn_heavy", "fleet_churn"])
def test_none_mode_replays_goldens_byte_exactly(name):
    """``preemption="none"`` is a pure refactor: replaying a pre-seam
    golden with the arbitration knob explicitly set (and a non-zero — and
    therefore provably inert — ctx overhead) reproduces the stored
    document byte for byte."""
    import json
    from pathlib import Path

    from repro.runtime.record_golden import dump_doc, record_scenario

    preset = dataclasses.replace(
        golden_scenario(name), preemption="none", gpu_ctx_overhead=0.37
    )
    stored = (Path(__file__).parent / "golden" / f"{name}.json").read_text()
    assert dump_doc(json.loads(json.dumps(record_scenario(preset)))) + "\n" \
        == stored


# ---- never-optimistic property ----------------------------------------------


def _check_preemptive_never_optimistic(seed: int) -> int:
    """Under priority arbitration with real preemptions, every completed
    job observes R ≤ the R̂ its admission epoch certified, and no deadline
    is missed.  Returns the number of GPU preemptions exercised."""
    events = generate_churn_trace(
        seed=seed, horizon=4000.0,
        config=ChurnConfig(mean_interarrival=120.0,
                           lifetime_range=(800.0, 2500.0),
                           util_range=(0.08, 0.2),
                           task_config=GeneratorConfig(n_subtasks=3)),
    )
    trace = EventTrace()
    res = simulate_churn(events, gn_total=6, horizon=5000.0, seed=seed,
                         preemption="priority", gpu_ctx_overhead=0.05,
                         trace=trace)
    assert not res.any_miss, f"misses under preemption: {res.misses}"
    assert res.bound_violations() == [], res.bound_violations()[:3]
    return _gpu_preempts(trace)


def test_preemptive_churn_exercises_preemptions_fixed_seed():
    """Deterministic anchor: this seed demonstrably contains preemptions,
    so the property below never degenerates to a vacuous pass."""
    assert _check_preemptive_never_optimistic(1) >= 1


try:
    from hypothesis import HealthCheck, assume, given, settings
    from hypothesis import strategies as st
except ImportError:      # pragma: no cover - optional dependency
    pass
else:
    @given(seed=st.integers(0, 10_000))
    @settings(max_examples=8, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    def test_preemptive_certification_never_optimistic(seed):
        preempts = _check_preemptive_never_optimistic(seed)
        # the property is about runs that actually preempt; most seeds in
        # this regime do, the rest are discarded (not a vacuous pass)
        assume(preempts >= 1)
