"""Indexed-engine equivalence suite (ISSUE 10).

The indexed event loop (`REPRO_ENGINE=indexed`, the default) must be
*bit-identical* to the scan-everything reference loop it replaced — not
statistically close: the golden corpus pins every event and float, so the
contract here is byte equality of traces and results.

Four layers of evidence:

  * every golden scenario replays identically through BOTH variants in
    the same test (a variant regression fails next to the oracle that
    exonerates the scenario itself);
  * hypothesis drives randomized churn — single-host and broker-routed
    fleet with migrations and elastic host adds — through both loops
    under both GPU arbitration modes and asserts event-by-event equality;
  * the zero-width-step livelock guard raises its diagnostic (policy
    name, timestamp, running set) instead of spinning, on both loops;
  * `engine_steps_total` / `engine_step_width` land in the metrics
    registry, and step counts agree across variants (same trajectory ⇒
    same step sequence).
"""
import dataclasses
import json
import math
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    ChurnConfig,
    ChurnEvent,
    GeneratorConfig,
    generate_churn_trace,
    generate_taskset,
    golden_scenario,
)
from repro.obs import metrics
from repro.runtime import simulate, simulate_churn, simulate_fleet
from repro.runtime.engine import DiscreteEventEngine, SchedulingPolicy
from repro.runtime.record_golden import record_scenario
from repro.runtime.simulator import _FleetChurnPolicy
from repro.sched import CapacityBroker, DynamicController, EventTrace

GOLDEN_DIR = Path(__file__).parent / "golden"
GOLDEN_FILES = sorted(GOLDEN_DIR.glob("*.json"))

VARIANTS = ("reference", "indexed")


# ---- golden corpus × both variants ------------------------------------------


@pytest.mark.parametrize(
    "path", GOLDEN_FILES, ids=[p.stem for p in GOLDEN_FILES]
)
def test_golden_replays_identically_under_both_variants(path, monkeypatch):
    """Each golden must replay byte-identically through the reference
    loop (the oracle) AND the default indexed loop — checked in one test
    so a divergence immediately shows which loop moved."""
    stored = json.loads(path.read_text())
    stored.pop("description", None)
    for variant in VARIANTS:
        monkeypatch.setenv("REPRO_ENGINE", variant)
        replayed = json.loads(json.dumps(
            record_scenario(golden_scenario(path.stem))
        ))
        replayed.pop("description", None)
        assert replayed == stored, (
            f"golden {path.stem!r} diverged under REPRO_ENGINE={variant}"
        )
    # and the default path (no env var) must be the indexed loop
    monkeypatch.delenv("REPRO_ENGINE", raising=False)
    replayed = json.loads(json.dumps(
        record_scenario(golden_scenario(path.stem))
    ))
    replayed.pop("description", None)
    assert replayed == stored, (
        f"golden {path.stem!r} diverged under the default engine"
    )


# ---- randomized churn: indexed ≡ reference ----------------------------------


def _run_churn(seed, gpu, variant):
    events = generate_churn_trace(
        seed=seed, horizon=2500.0,
        config=ChurnConfig(mean_interarrival=150.0,
                           lifetime_range=(400.0, 1500.0)),
    )
    preemption, ctx = gpu
    trace = EventTrace()
    res = simulate_churn(
        events, gn_total=8, horizon=3000.0, seed=seed,
        trace=trace, preemption=preemption, gpu_ctx_overhead=ctx,
        engine_variant=variant,
    )
    return trace, res


def _run_fleet(seed, gpu, variant):
    events = generate_churn_trace(
        seed=seed, horizon=2500.0,
        config=ChurnConfig(mean_interarrival=120.0,
                           lifetime_range=(400.0, 1500.0)),
    )
    preemption, ctx = gpu
    trace = EventTrace()
    res = simulate_fleet(
        events, n_hosts=3, gn_per_host=6, horizon=3000.0, seed=seed,
        imbalance_threshold=0.2, max_migrations_per_event=2,
        trace=trace, preemption=preemption, gpu_ctx_overhead=ctx,
        elastic=[(600.0, "add", 6), (1400.0, "retire", 1)],
        engine_variant=variant,
    )
    return trace, res


def _assert_trace_equal(ref_trace, idx_trace, label):
    if ref_trace.events != idx_trace.events:
        div = ref_trace.diff(idx_trace)
        idx, want, got = div
        pytest.fail(
            f"{label}: engines diverged at event {idx}/"
            f"{len(ref_trace.events)}:\n"
            f"  reference: {want.as_dict() if want else '<end>'}\n"
            f"  indexed:   {got.as_dict() if got else '<end>'}"
        )


GPU_MODES = [("none", 0.0), ("priority", 0.35)]
GPU_IDS = ["gpu-none", "gpu-priority"]


def _check_churn_equivalent(seed, gpu):
    ref_trace, ref = _run_churn(seed, gpu, "reference")
    idx_trace, idx = _run_churn(seed, gpu, "indexed")
    _assert_trace_equal(ref_trace, idx_trace, f"churn seed={seed} gpu={gpu}")
    assert ref.responses == idx.responses
    assert ref.bounds == idx.bounds
    assert ref.misses == idx.misses
    assert ref.admitted == idx.admitted and ref.rejected == idx.rejected


def _check_fleet_equivalent(seed, gpu):
    ref_trace, ref = _run_fleet(seed, gpu, "reference")
    idx_trace, idx = _run_fleet(seed, gpu, "indexed")
    _assert_trace_equal(ref_trace, idx_trace, f"fleet seed={seed} gpu={gpu}")
    assert ref.responses == idx.responses
    assert ref.bounds == idx.bounds
    assert ref.misses == idx.misses
    assert ref.placements == idx.placements
    assert ref.migrations == idx.migrations
    assert ref.fleet_events == idx.fleet_events


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - dev env always has hypothesis
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @given(seed=st.integers(0, 10_000),
           gpu=st.sampled_from(GPU_MODES))
    @settings(max_examples=10, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    def test_churn_traces_bit_identical_across_variants(seed, gpu):
        _check_churn_equivalent(seed, gpu)

    @given(seed=st.integers(0, 10_000),
           gpu=st.sampled_from(GPU_MODES))
    @settings(max_examples=6, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    def test_fleet_traces_bit_identical_across_variants(seed, gpu):
        """Fleet churn exercises every index invalidation path: admits,
        boundary reclaims, migrations (membership leaves one group and
        joins another mid-run), elastic host add and retire."""
        _check_fleet_equivalent(seed, gpu)

else:  # pragma: no cover

    @pytest.mark.parametrize("seed", [0, 7])
    @pytest.mark.parametrize("gpu", GPU_MODES, ids=GPU_IDS)
    def test_churn_traces_bit_identical_across_variants(seed, gpu):
        _check_churn_equivalent(seed, gpu)

    @pytest.mark.parametrize("seed", [0, 7])
    @pytest.mark.parametrize("gpu", GPU_MODES, ids=GPU_IDS)
    def test_fleet_traces_bit_identical_across_variants(seed, gpu):
        _check_fleet_equivalent(seed, gpu)


# ---- broker-routed fleet churn at a hundred residents -----------------------


def _fleet_pool(seed=5, n=16, gn=8):
    """``n`` single-service shapes and the mean slices one takes alone on
    a ``gn``-slice host."""
    rng = np.random.default_rng(seed)
    pool = [
        dataclasses.replace(
            generate_taskset(rng, 0.05, GeneratorConfig(n_tasks=1,
                                                        n_subtasks=3))[0],
            name=f"pool{i}")
        for i in range(n)
    ]
    probe = DynamicController(gn, transition="instant")
    allocs = []
    for t in pool:
        if probe.admit(t).admitted:
            allocs.append(probe.allocation[t.name])
            probe.release(t.name)
    return pool, float(np.mean(allocs))


def _fleet_hosts(level, g_mean, gn=8):
    """30 % headroom, so that every admission and re-admission fits."""
    return math.ceil(level * g_mean / gn * 1.3)


def _fleet_churn(level, pool, seed=7):
    """``level`` pool services admitted over [0, 400), then an eighth of
    them released in [400, 700) and each re-admitted 80 later."""
    rng = np.random.default_rng(seed)
    events = [
        ChurnEvent(time=round(400.0 * i / level, 6), kind="admit",
                   name=f"svc{i}",
                   task=dataclasses.replace(pool[i % len(pool)],
                                            name=f"svc{i}"))
        for i in range(level)
    ]
    for v in sorted(int(x) for x in rng.choice(level, size=level // 8,
                                               replace=False)):
        t = float(rng.uniform(400.0, 700.0))
        events.append(ChurnEvent(time=t, kind="release", name=f"svc{v}"))
        events.append(ChurnEvent(
            time=t + 80.0, kind="admit", name=f"svc{v}",
            task=dataclasses.replace(pool[v % len(pool)], name=f"svc{v}")))
    events.sort(key=lambda e: (e.time, e.name))
    return events


def _fleet_engine(variant, level=100, trace=None):
    pool, g_mean = _fleet_pool()
    broker = CapacityBroker.build(
        _fleet_hosts(level, g_mean), 8, transition="boundary",
        engine="batch", trace=trace,
    )
    policy = _FleetChurnPolicy(_fleet_churn(level, pool), broker,
                               np.random.default_rng(11),
                               release_jitter=True, worst_case=False)
    return DiscreteEventEngine(policy, trace=trace, variant=variant)


def test_hundred_resident_fleet_churn_identical_across_variants():
    """A hundred residents on broker-routed hosts with live churn: both
    loops take the same steps and record the same events."""
    runs = {}
    for variant in VARIANTS:
        trace = EventTrace()
        eng = _fleet_engine(variant, trace=trace)
        eng.run(800.0)
        runs[variant] = (eng.steps, trace)
    (ref_steps, ref_trace), (idx_steps, idx_trace) = (
        runs["reference"], runs["indexed"])
    assert ref_steps == idx_steps > 0
    _assert_trace_equal(ref_trace, idx_trace, "hundred-resident fleet churn")


@pytest.mark.parametrize("variant", VARIANTS)
def test_seg_kind_probes_per_step(variant):
    """The reference loop probes a member's segment kind at most once per
    member per step; the indexed loop keeps kinds itself and never
    probes."""
    eng = _fleet_engine(variant)
    calls = 0
    probe = eng.seg_kind

    def counting(key):
        nonlocal calls
        calls += 1
        return probe(key)

    eng.seg_kind = counting
    eng.run(800.0)
    assert eng.steps > 0
    if variant == "indexed":
        assert calls == 0
    else:
        assert 0 < calls <= 100 * eng.steps


def test_simulate_fleet_admits_every_service_with_headroom():
    """``simulate_fleet`` on the default loop places all hundred services
    of the churn above (its admissions, which all precede t=400)."""
    pool, g_mean = _fleet_pool()
    res = simulate_fleet(_fleet_churn(100, pool)[:100],
                         _fleet_hosts(100, g_mean), 8, horizon=500.0, seed=1)
    assert len(res.admitted) == 100
    assert sum(res.jobs.values()) > 0


# ---- zero-width-step livelock guard -----------------------------------------


class _StuckPolicy(SchedulingPolicy):
    """Pathological policy pinning `next_external_time` at t=0 forever:
    every step has dt == 0 and the clock never advances."""

    incremental = True  # let the indexed loop accept it too

    def release_jobs(self, now):
        pass

    def arbitration_order(self):
        return []

    def resource_groups(self):
        return [None]

    def next_external_time(self, now):
        return 0.0

    def on_job_complete(self, key, job, now, response):  # pragma: no cover
        pass


@pytest.mark.parametrize("variant", VARIANTS)
def test_livelock_guard_raises_diagnostic(variant):
    eng = DiscreteEventEngine(_StuckPolicy(), variant=variant)
    eng.max_same_time_steps = 50
    with pytest.raises(RuntimeError) as exc:
        eng.run(horizon=100.0)
    msg = str(exc.value)
    assert "_StuckPolicy" in msg          # which policy wedged
    assert "t=0.0" in msg                 # at what timestamp
    assert "running:" in msg              # what was (not) running


@pytest.mark.parametrize("variant", VARIANTS)
def test_healthy_runs_stay_under_the_guard(variant, monkeypatch):
    """Real workloads emit same-timestamp bursts (simultaneous releases,
    completion→release cascades) but always make progress — the guard
    must never fire on a golden scenario."""
    monkeypatch.setenv("REPRO_ENGINE", variant)
    record_scenario(golden_scenario("churn_heavy"))  # raises on livelock


# ---- step metrics -----------------------------------------------------------


def _small_taskset(seed):
    import numpy as np

    from repro.core import GeneratorConfig, generate_taskset, schedule

    rng = np.random.default_rng(seed)
    ts = generate_taskset(rng, 0.5, GeneratorConfig(variability=0.3))
    res = schedule(ts, 10, mode="greedy")
    return ts, list(res.alloc)


def test_engine_step_metrics_recorded():
    reg = metrics.enable(fresh=True)
    try:
        ts, alloc = _small_taskset(3)
        simulate(ts, alloc, horizon=400.0, seed=3)
        snap = reg.snapshot()
        steps = reg.value("engine_steps_total")
        assert steps is not None and steps > 0
        hist = snap["engine_step_width"]["series"][""]
        assert hist["count"] == steps       # one width observed per step
        assert hist["sum"] > 0.0            # the clock actually advanced
    finally:
        metrics.disable()


def test_step_counter_equal_across_variants():
    """Bit-identical trajectories must take the identical step sequence,
    so the two loops must agree on `engine.steps` exactly."""
    ts, alloc = _small_taskset(11)
    counts = {}
    for variant in VARIANTS:
        from repro.runtime.simulator import _FixedTaskSetPolicy
        import numpy as np

        policy = _FixedTaskSetPolicy(
            ts, alloc, np.random.default_rng(11), True, False,
        )
        eng = DiscreteEventEngine(policy, variant=variant)
        eng.run(600.0)
        counts[variant] = eng.steps
    assert counts["reference"] == counts["indexed"] > 0
