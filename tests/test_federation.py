"""Multi-host federation tests: broker placement, rejection fallback,
departure-imbalance migration, and the layered-stack seams they ride on.

The load-bearing properties (ISSUE 4 acceptance):

  * a ≥3-host broker scenario admits, migrates on departure imbalance,
    and the churn simulator validates observed R ≤ certified R̂ for every
    task on every host (no deadline can be missed mid-migration);
  * fleet admission falls through to the next host on rejection — the
    fleet only rejects once every host has;
  * post-refactor layering is clean: the slice ledger (capacity.py) and
    certification engines (certify.py) are reusable without the
    controller, and the controller exposes the per-task analysis the
    admission wrapper used to re-derive.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core import (
    ChurnConfig,
    GeneratorConfig,
    generate_churn_trace,
    generate_taskset,
)
from repro.core.rta import AnalysisTables
from repro.runtime import AdmissionController, simulate_fleet
from repro.sched import (
    BatchCertifier,
    CapacityBroker,
    DynamicController,
    Entry,
    EventTrace,
    ScalarCertifier,
    SlicePool,
)


def _tasks(seed=0, util=0.5, n=6, m=3):
    rng = np.random.default_rng(seed)
    return list(generate_taskset(
        rng, util, GeneratorConfig(n_tasks=n, n_subtasks=m)
    ))


def _task(seed, util, name):
    t = _tasks(seed=seed, util=util, n=1)[0]
    return dataclasses.replace(t, name=name)


class TestPlacementPolicies:
    def _loaded_broker(self):
        """3 hosts with free capacity 4 / 2 / 6 (instant mode)."""
        broker = CapacityBroker.build(3, 8, transition="instant",
                                      migrate_on_departure=False)
        # occupy hosts unevenly with small direct admissions
        for h, n_tasks in ((0, 4), (1, 6), (2, 2)):
            for i in range(n_tasks):
                t = _task(seed=10 + h * 8 + i, util=0.04, name=f"h{h}x{i}")
                dec = broker.hosts[h].admit(t)
                assert dec.admitted
        # normalize: exactly 1 slice per filler task
        for h, free in ((0, 4), (1, 2), (2, 6)):
            assert broker.hosts[h].free_capacity == free, h
        return broker

    def test_least_loaded_prefers_most_free(self):
        broker = self._loaded_broker()
        broker.placement = "least_loaded"
        t = _task(seed=99, util=0.05, name="new")
        dec = broker.admit(t)
        assert dec.admitted and dec.host == 2
        assert dec.tried_hosts[0] == 2

    def test_best_fit_prefers_tightest(self):
        broker = self._loaded_broker()
        broker.placement = "best_fit"
        t = _task(seed=99, util=0.05, name="new")
        dec = broker.admit(t)
        assert dec.admitted and dec.host == 1
        assert dec.tried_hosts[0] == 1

    def test_first_fit_takes_index_order(self):
        broker = self._loaded_broker()
        broker.placement = "first_fit"
        t = _task(seed=99, util=0.05, name="new")
        dec = broker.admit(t)
        assert dec.admitted and dec.host == 0

    def test_callable_placement(self):
        broker = self._loaded_broker()
        broker.placement = lambda b, task: [1, 0, 2]
        t = _task(seed=99, util=0.05, name="new")
        dec = broker.admit(t)
        assert dec.admitted and dec.host == 1

    def test_unknown_placement_rejected(self):
        with pytest.raises(ValueError):
            CapacityBroker.build(2, 4, placement="round_robin")


class TestRejectionFallback:
    def test_falls_through_to_next_host(self):
        """A task too big for the tight host lands on the roomy one."""
        broker = CapacityBroker.build(2, 8, transition="instant",
                                      placement="first_fit",
                                      migrate_on_departure=False)
        # host 0: nearly full
        for i in range(7):
            assert broker.hosts[0].admit(
                _task(seed=20 + i, util=0.04, name=f"f{i}")
            ).admitted
        big = _task(seed=50, util=1.2, name="big")   # needs ~4 slices
        dec = broker.admit(big)
        assert dec.admitted
        assert dec.host == 1
        assert list(dec.tried_hosts) == [0, 1]
        assert broker.active_host("big") == 1
        assert broker.hosts[1].allocation["big"] > \
            broker.hosts[0].free_capacity

    def test_fleet_rejects_only_after_every_host(self):
        broker = CapacityBroker.build(3, 4, transition="instant")
        impossible = _task(seed=7, util=40.0, name="huge")
        dec = broker.admit(impossible)
        assert not dec.admitted
        assert len(dec.tried_hosts) == 3
        assert "rejected by all 3 hosts" in dec.reason
        # per-host transactionality: nothing resident anywhere
        assert broker.allocation == {}

    def test_realloc_pass_skips_repeated_pinned_sweep(self):
        """Pass 2 goes straight to the re-balance search: admit(...,
        pinned=False) never runs the pinned sweep, and decides identically
        to the full call (pass-1 rejection was transactional)."""
        c_full = DynamicController(8, transition="instant")
        c_skip = DynamicController(8, transition="instant")
        for i in range(7):
            t = _task(seed=20 + i, util=0.04, name=f"f{i}")
            assert c_full.admit(t).admitted
            assert c_skip.admit(t).admitted
        big = _task(seed=50, util=1.2, name="big")   # pinned can't fit it
        d_full = c_full.admit(big)

        def _no_pinned(*a, **k):
            raise AssertionError("pinned sweep ran despite pinned=False")

        c_skip._certifier.pinned_sweep = _no_pinned
        d_skip = c_skip.admit(big, pinned=False)
        assert d_full.admitted == d_skip.admitted
        assert d_full.alloc == d_skip.alloc
        assert d_full.bounds == d_skip.bounds

    def test_duplicate_fleet_name_rejected(self):
        broker = CapacityBroker.build(2, 8, transition="instant")
        t = _task(seed=1, util=0.1, name="svc")
        assert broker.admit(t).admitted
        dec = broker.admit(dataclasses.replace(t, name="svc"))
        assert not dec.admitted and "already resident" in dec.reason


class TestMigration:
    def _imbalanced_broker(self, **kw):
        """Boundary-mode 2-host fleet, host 0 heavily loaded (first-fit
        packs every arrival there while it certifies)."""
        broker = CapacityBroker.build(
            2, 8, transition="boundary", imbalance_threshold=0.2,
            placement="first_fit", **kw
        )
        for i in range(6):
            t = _task(seed=30 + i, util=0.05, name=f"m{i}")
            dec = broker.admit(t)
            assert dec.admitted and dec.host == 0
        return broker

    def test_departure_triggers_certified_migration(self):
        broker = self._imbalanced_broker()
        # depart one task; at its (idle) boundary the imbalance is visible
        assert broker.release("m0")
        assert broker.job_boundary("m0") == "reclaimed"
        # one migration started: admitted on host 1, departing on host 0
        assert len(broker.migrating) == 1
        (name, mig), = broker.migrating.items()
        assert mig.src == 0 and mig.dst == 1
        assert name in broker.hosts[1].allocation      # certified on target
        assert broker.hosts[0].is_departing(name)      # release-at-boundary
        assert broker.active_host(name) == 0           # jobs still on source
        # the migrant is NOT a fleet departure
        assert not broker.is_departing(name)
        # source boundary completes the move
        assert broker.job_boundary(name) == "migrated"
        assert broker.active_host(name) == 1
        assert name not in broker.hosts[0].allocation
        assert broker.migration_log[-1].name == name

    def test_instant_departures_drain_by_migration(self):
        """Two 28-slice hosts, first-fit packing the arrivals onto host 0,
        then its residents depart one by one in instant mode: the
        imbalance moves services between the hosts, each move inline and
        certified."""
        broker = CapacityBroker.build(
            2, 28, transition="instant", engine="batch",
            placement="first_fit", imbalance_threshold=0.2,
            max_migrations_per_event=1,
        )
        events = generate_churn_trace(
            seed=2, horizon=2500.0,
            config=ChurnConfig(mean_interarrival=110.0,
                               lifetime_range=(3500.0, 7000.0),
                               util_range=(0.02, 0.05),
                               task_config=GeneratorConfig(n_subtasks=3)),
        )
        admitted = [ev.name for ev in events
                    if ev.kind == "admit" and broker.admit(ev.task).admitted]
        moved = 0
        for name in admitted:
            if broker.active_host(name) != 0:
                continue                   # migrated away already
            before = len(broker.migration_log)
            assert broker.release(name)
            moved += len(broker.migration_log) - before
        assert moved > 0
        assert not broker.migrating        # instant mode: nothing in flight
        assert broker.allocation
        for name in broker.allocation:
            assert broker.bound(name) != math.inf

    def test_migration_not_started_when_target_rejects(self):
        broker = self._imbalanced_broker()
        # fill host 1 so no candidate certifies there
        for i in range(8):
            t = _task(seed=60 + i, util=0.05, name=f"fill{i}")
            if not broker.hosts[1].admit(t).admitted:
                break
        free_before = broker.hosts[1].free_capacity
        broker.release("m0")
        broker.job_boundary("m0")
        if broker.migrating:
            # a migration only starts when the target certified the task
            (name, mig), = broker.migrating.items()
            assert name in broker.hosts[1].allocation
        else:
            assert broker.hosts[1].free_capacity == free_before

    def test_release_mid_migration_departs_both_sides(self):
        broker = self._imbalanced_broker()
        broker.release("m0")
        broker.job_boundary("m0")
        (name, mig), = broker.migrating.items()
        assert broker.release(name)
        assert not broker.migrating                 # move cancelled
        assert name not in broker.hosts[mig.dst].allocation  # idle copy gone
        assert broker.hosts[mig.src].is_departing(name)
        assert broker.job_boundary(name) == "reclaimed"
        assert broker.active_host(name) is None

    def test_update_rate_mid_migration_lands_on_target(self):
        """A migrating task's rate change is staged on the migration
        target (its home for every post-boundary job), not refused by the
        departing source copy."""
        broker = self._imbalanced_broker()
        broker.release("m0")
        broker.job_boundary("m0")
        (name, mig), = broker.migrating.items()
        old = broker.hosts[mig.dst].task(name)
        dec = broker.update_rate(name, old.period * 2, old.deadline * 1.5)
        assert dec.admitted, dec.reason
        # staged on the target; the source copy keeps its old certified rate
        assert broker.hosts[mig.dst].in_transition(name)
        assert broker.hosts[mig.src].task(name).period == old.period
        # complete the move, commit the stage at the first target boundary
        assert broker.job_boundary(name) == "migrated"
        assert broker.job_boundary(name) == "committed"
        assert broker.task(name).period == old.period * 2

    def test_broker_trace_records_migrations_host_tagged(self):
        trace = EventTrace(label="fleet")
        broker = CapacityBroker.build(
            2, 8, transition="boundary", imbalance_threshold=0.2,
            placement="first_fit", trace=trace,
        )
        for i in range(6):
            t = _task(seed=30 + i, util=0.05, name=f"m{i}")
            dec = broker.admit(t)
            assert dec.admitted and dec.host == 0
        broker.release("m0")
        broker.job_boundary("m0")
        kinds = trace.counts()
        assert kinds.get("migrate", 0) == len(broker.migrating) == 1
        mig_ev = [e for e in trace.events if e.kind == "migrate"][0]
        meta = dict(mig_ev.meta)
        assert meta["src"] == 0 and meta["dst"] == 1
        # chrome export renders one process lane group per host
        doc = trace.to_chrome()
        procs = {r["pid"]: r["args"]["name"] for r in doc["traceEvents"]
                 if r["name"] == "process_name"}
        assert procs == {1: "fleet/host0", 2: "fleet/host1"}


class TestFleetSimulation:
    def test_three_host_churn_migrates_and_holds_bounds(self):
        """ISSUE acceptance: ≥3 hosts, admissions + migrations end to end,
        observed R ≤ certified R̂ for every job on every host."""
        events = generate_churn_trace(
            seed=0, horizon=6000.0,
            config=ChurnConfig(mean_interarrival=150.0,
                               lifetime_range=(800.0, 2500.0)),
        )
        trace = EventTrace(label="fleet")
        res = simulate_fleet(events, n_hosts=3, gn_per_host=6,
                             horizon=7000.0, seed=0, trace=trace)
        assert len(res.admitted) >= 10
        assert res.total_jobs >= 50
        assert not res.any_miss, f"misses: {res.misses}"
        assert res.bound_violations() == []
        assert res.migrations, "scenario must exercise migration"
        assert {m["src"] for m in res.migrations} | \
               {m["dst"] for m in res.migrations} <= {0, 1, 2}
        # every admitted service is placed on a real host
        assert set(res.placements) == set(res.admitted)
        assert set(res.placements.values()) <= {0, 1, 2}
        # trace is host-tagged: every event carries a host lane
        hosts_seen = {dict(e.meta).get("host") for e in trace.events}
        assert hosts_seen <= {0, 1, 2} and len(hosts_seen) == 3

    def test_fleet_run_is_deterministic(self):
        events = generate_churn_trace(
            seed=4, horizon=3000.0,
            config=ChurnConfig(mean_interarrival=200.0,
                               lifetime_range=(600.0, 1500.0)),
        )
        t1, t2 = EventTrace(), EventTrace()
        r1 = simulate_fleet(events, 3, 6, 3500.0, seed=4, trace=t1)
        r2 = simulate_fleet(events, 3, 6, 3500.0, seed=4, trace=t2)
        assert t1.dumps() == t2.dumps()
        assert r1.responses == r2.responses
        assert r1.migrations == r2.migrations

    def test_single_host_fleet_matches_churn_semantics(self):
        """A 1-host broker with migrations off behaves like simulate_churn
        for the same trace (same admissions, jobs, and miss counts)."""
        from repro.runtime import simulate_churn

        events = generate_churn_trace(seed=2, horizon=3000.0,
                                      config=ChurnConfig())
        churn = simulate_churn(events, 10, 3500.0, seed=2)
        fleet = simulate_fleet(events, 1, 10, 3500.0, seed=2)
        assert fleet.admitted == churn.admitted
        assert fleet.rejected == churn.rejected
        assert fleet.jobs == churn.jobs
        assert fleet.misses == churn.misses
        assert fleet.responses == churn.responses
        assert fleet.migrations == []

    def test_instant_host_rejected_by_simulator(self):
        broker = CapacityBroker.build(2, 6, transition="instant")
        with pytest.raises(ValueError):
            simulate_fleet([], 2, 6, 100.0, broker=broker)


class TestLayeredStack:
    """The refactor seams: ledger and certifiers reusable standalone."""

    def test_slice_pool_fork_adopt_transactionality(self):
        pool = SlicePool(8)
        t = _task(seed=1, util=0.1, name="a")
        pool.reserve(Entry(task=t, alloc=3))
        fp = pool.fingerprint()
        fork = pool.fork()
        fork.reserve(Entry(task=_task(seed=2, util=0.1, name="b"), alloc=2))
        fork.get("a").departing = True
        assert pool.fingerprint() == fp          # fork mutation is isolated
        assert fork.capacity_in_use == 5
        pool.adopt(fork)
        assert pool.capacity_in_use == 5 and pool.get("a").departing

    def test_pool_envelope_capacity_counts_staged(self):
        pool = SlicePool(10)
        t = _task(seed=3, util=0.1, name="a")
        e = Entry(task=t, alloc=2, staged_alloc=5)
        pool.reserve(e)
        assert e.gn_lo == 2 and e.gn_hi == 5
        assert pool.capacity_in_use == 5         # envelope, not committed
        e.commit()
        assert e.alloc == 5 and not e.in_transition

    def test_certifiers_agree_standalone(self):
        """Scalar and batched certification agree without any controller."""
        entries = [
            Entry(task=t, alloc=2)
            for t in _tasks(seed=5, util=0.4, n=4)
        ]
        scalar = ScalarCertifier(tightened=True)
        batch = BatchCertifier(tightened=True, min_work=1)
        arrival = _task(seed=9, util=0.08, name="new")
        s = scalar.pinned_sweep(arrival, entries, AnalysisTables(), {}, 1, 4)
        b = batch.pinned_sweep(arrival, entries, AnalysisTables(), {}, 1, 4)
        assert s[0] == b[0]
        if s[0] is not None:
            assert s[1] == b[1]

    def test_controller_exposes_set_analysis(self):
        c = DynamicController(8, transition="instant")
        for t in _tasks(seed=0, util=0.4, n=4):
            c.admit(t)
        sa = c.set_analysis()
        assert sa is not None and sa.schedulable
        ts = c.current_taskset()
        assert [ta.name for ta in sa.tasks] == [t.name for t in ts]
        # the certified bounds and the re-materialized analyses agree
        for ta in sa.tasks:
            assert ta.response <= c.bound(ta.name) + 1e-9

    def test_admission_wrapper_attaches_controller_analysis(self):
        ac = AdmissionController(gn_total=8)
        t = _task(seed=0, util=0.2, name="svc")
        dec = ac.admit(t)
        assert dec.admitted and dec.result is not None
        assert dec.result.schedulable
        assert [ta.name for ta in dec.result.analysis.tasks] == ["svc"]

    def test_multi_host_admission_wrapper(self):
        ac = AdmissionController(gn_total=6, hosts=3)
        names = []
        for i in range(6):
            t = _task(seed=40 + i, util=0.15, name=f"svc{i}")
            dec = ac.admit(t)
            assert dec.admitted and dec.host in (0, 1, 2)
            assert dec.result is not None and dec.result.schedulable
            names.append(t.name)
        assert set(ac.allocation) == set(names)
        # least-loaded default spreads across hosts
        assert len({ac.broker.active_host(n) for n in names}) >= 2
        assert ac.remove(names[0])
        assert names[0] not in ac.allocation
        with pytest.raises(AttributeError):
            ac.dynamic


class TestServingWithBroker:
    def test_engine_registers_on_fleet(self):
        from repro.configs import get_smoke_config
        from repro.runtime import ServingTaskSpec
        from repro.serving import ServeConfig, ServingEngine

        cfg = get_smoke_config("qwen3-0.6b")
        eng = ServingEngine(cfg, ServeConfig(max_context=64, batch=2))
        broker = CapacityBroker.build(2, 8)
        spec = ServingTaskSpec(
            name="svc", arch_id="qwen3-0.6b", period_ms=50.0,
            deadline_ms=40.0, batch=2, seq_len=64, new_tokens=2,
            roofline_step_s=0.002, collective_s=2e-4, dominant="compute_s",
        )
        dec = eng.rt_register(broker, spec)
        assert dec.admitted and eng.rt_registered
        assert dec.host is not None
        assert broker.active_host("svc") == dec.host
        assert math.isfinite(broker.bound("svc"))
        assert eng.rt_deregister()
        assert broker.is_departing("svc")
        assert broker.job_boundary("svc") == "reclaimed"
        assert broker.active_host("svc") is None


class TestHeterogeneousFleet:
    """register_placement + the "weighted" policy + speed-normalized
    imbalance (ISSUE 5 satellite: heterogeneous fleets)."""

    def test_register_placement_custom_policy(self):
        from repro.sched import register_placement
        from repro.sched.federation import PLACEMENT_POLICIES

        name = "reverse_index_test_only"
        try:
            register_placement(name, lambda b, task:
                               list(range(len(b.hosts)))[::-1])
            broker = CapacityBroker.build(3, 8, transition="instant",
                                          placement=name,
                                          migrate_on_departure=False)
            dec = broker.admit(_task(seed=99, util=0.05, name="new"))
            assert dec.admitted and dec.host == 2
            assert dec.tried_hosts[0] == 2
        finally:
            PLACEMENT_POLICIES.pop(name, None)

    def test_register_placement_validates(self):
        from repro.sched import register_placement

        with pytest.raises(ValueError, match="built-in"):
            register_placement("least_loaded", lambda b, t: [0])
        with pytest.raises(TypeError):
            register_placement("not_callable_test_only", 3)

    def test_host_speeds_validated(self):
        with pytest.raises(ValueError, match="entries"):
            CapacityBroker.build(3, 8, host_speeds=[1.0, 2.0])
        with pytest.raises(ValueError, match="positive"):
            CapacityBroker.build(2, 8, host_speeds=[1.0, 0.0])

    def test_weighted_prefers_effective_free_capacity(self):
        """Equal free slices everywhere: the fastest host wins; with unit
        speeds "weighted" degenerates to exactly "least_loaded"."""
        broker = CapacityBroker.build(
            3, 8, transition="instant", placement="weighted",
            migrate_on_departure=False, host_speeds=[1.0, 2.0, 1.5],
        )
        assert broker._placement_order(None) == [1, 2, 0]
        uniform = CapacityBroker.build(3, 8, placement="weighted")
        from repro.sched.federation import PLACEMENT_POLICIES

        assert uniform._placement_order(None) == \
            PLACEMENT_POLICIES["least_loaded"](uniform, None)

    def test_load_normalized_by_speed(self):
        broker = CapacityBroker.build(2, 8, transition="instant",
                                      migrate_on_departure=False,
                                      host_speeds=[1.0, 2.0])
        for h in (0, 1):
            t = _task(seed=40 + h, util=0.04, name=f"f{h}")
            assert broker.hosts[h].admit(t).admitted
            assert broker.hosts[h].capacity_in_use == 1
        assert broker.load(0) == pytest.approx(1 / 8)
        assert broker.load(1) == pytest.approx(1 / 16)

    def test_migration_balances_toward_fast_host(self):
        """A slice split that looks balanced raw is imbalanced in
        effective-capacity terms: the broker migrates toward the fast
        host, and the homogeneous twin of the same fleet does not."""
        def build(speeds):
            broker = CapacityBroker.build(
                2, 8, transition="instant", placement="first_fit",
                imbalance_threshold=0.45, max_migrations_per_event=2,
                host_speeds=speeds,
            )
            names = []
            for i in range(6):
                t = _task(seed=60 + i, util=0.04, name=f"m{i}")
                assert broker.hosts[0].admit(t).admitted
                broker._active[t.name] = 0
                names.append(t.name)
            for i in range(2):
                t = _task(seed=80 + i, util=0.04, name=f"d{i}")
                assert broker.hosts[1].admit(t).admitted
                broker._active[t.name] = 1
            return broker, names

        # after the release: raw loads 5/8 vs 2/8 (gap 0.375 < 0.45, no
        # move on identical hosts) but with host 1 at speed 2 the effective
        # gap is 0.625 - 0.125 = 0.5 > 0.45 — the broker migrates
        hom, names = build(None)
        hom.release(names[0])
        assert not hom.migration_log, "raw gap is under the threshold"
        het, names = build([1.0, 2.0])
        het.release(names[0])
        assert het.migration_log, "no migration despite effective imbalance"
        mig = het.migration_log[0]
        assert (mig.src, mig.dst) == (0, 1)

    def test_simulate_fleet_host_speeds_end_to_end(self):
        events = generate_churn_trace(
            seed=3, horizon=2500.0,
            config=ChurnConfig(mean_interarrival=200.0,
                               lifetime_range=(600.0, 1800.0)),
        )
        res = simulate_fleet(events, n_hosts=2, gn_per_host=6,
                             horizon=3000.0, seed=3, placement="weighted",
                             host_speeds=[1.0, 1.5])
        assert res.total_jobs > 0
        assert not res.any_miss
        assert res.bound_violations() == []
