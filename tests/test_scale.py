"""Fleet-scale admission: vectorized placement identity, incremental
ledger accounting, elastic fleets, hierarchical broker sharding.

The contract under test is *decision identity at scale*: the vectorized
placement sweep, the incrementally-maintained free-slice arrays, the
memo overlay, and the broker-tree digests are pure performance
machinery — every observable decision must be bit-identical to the
scalar reference implementations they replace.  Elastic membership
(``add_host`` / drain-then-retire) must additionally never cost a
deadline: scale-in goes through the certified two-phase migration
protocol, validated end to end in the discrete-event fleet simulator.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import ChurnEvent, GeneratorConfig, generate_taskset
from repro.obs import metrics
from repro.runtime import simulate_fleet
from repro.sched import (
    BrokerTree,
    CapacityBroker,
    DynamicController,
    Journal,
    MemoOverlay,
    SlicePool,
    recover_broker,
    serialize_state,
)
from repro.sched import capacity as capacity_mod
from repro.sched.federation import PLACEMENT_POLICIES

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

GN = 8


def _task(seed: int, util: float, name: str):
    rng = np.random.default_rng(seed)
    t = generate_taskset(
        rng, util, GeneratorConfig(n_tasks=1, n_subtasks=3)
    )[0]
    return dataclasses.replace(t, name=name)


def _pool(seed: int = 3, n: int = 8, util: float = 0.05):
    return [_task(seed * 100 + i, util, f"pool{i}") for i in range(n)]


@pytest.fixture(autouse=True)
def _metrics_off():
    """Every test starts and ends with the default (disabled) registry."""
    metrics.disable()
    yield
    metrics.disable()


# ---------------------------------------------------------------------------
# vectorized placement == scalar reference oracle
# ---------------------------------------------------------------------------


def _random_fleet(seed: int, n_hosts: int = 12, drain: int = 2):
    """A broker in a randomized state: heterogeneous speeds, random
    occupancy, a few drained hosts (placement must mask them)."""
    rng = np.random.default_rng(seed)
    speeds = rng.choice([0.5, 1.0, 1.0, 2.0], size=n_hosts).tolist()
    broker = CapacityBroker.build(
        n_hosts, GN, transition="instant", engine="batch",
        migrate_on_departure=False, host_speeds=speeds,
    )
    pool = _pool(seed)
    for i in range(int(rng.integers(0, 4 * n_hosts))):
        t = dataclasses.replace(
            pool[int(rng.integers(len(pool)))], name=f"f{seed}t{i}"
        )
        broker.admit(t)
    for h in rng.choice(n_hosts, size=min(drain, n_hosts - 1),
                        replace=False):
        broker._draining.add(int(h))
    return broker


def _assert_orders_identical(broker):
    inactive = broker._draining | broker._retired
    for policy in sorted(broker._VECTOR_POLICIES):
        vec = broker._vector_order(policy)
        ref = [h for h in PLACEMENT_POLICIES[policy](broker, None)
               if h not in inactive]
        assert vec == ref, (
            f"policy {policy!r}: vectorized {vec} != scalar {ref}"
        )


class TestPlacementEquivalence:
    def test_seeded_fleet_states(self):
        for seed in range(12):
            _assert_orders_identical(_random_fleet(seed))

    def test_wide_fleet_states(self):
        """48 hosts of mixed speeds, random occupancy, three drained."""
        for seed in range(4):
            _assert_orders_identical(_random_fleet(seed, n_hosts=48,
                                                   drain=3))

    def test_weighted_honors_speed_classes(self):
        # slower host with more free slices must lose to a faster one
        # with fewer when free * speed says so — in both implementations
        broker = CapacityBroker.build(
            3, GN, transition="instant", host_speeds=[1.0, 4.0, 1.0],
            placement="weighted", migrate_on_departure=False,
        )
        # occupy host 1 so it has fewer free slices but more weighted
        assert broker.hosts[1].admit(_task(7, 0.05, "a")).admitted
        free = [ctl.free_capacity for ctl in broker.hosts]
        assert free[1] < free[0]
        assert broker._vector_order("weighted")[0] == 1
        _assert_orders_identical(broker)

    def test_admission_identical_to_scalar_path(self):
        """End to end: a broker forced down the scalar path (custom
        callable wrapping the builtin) must place an identical arrival
        stream identically to the vectorized builtin."""
        for policy in sorted(CapacityBroker._VECTOR_POLICIES):
            fn = PLACEMENT_POLICIES[policy]
            vec = CapacityBroker.build(4, GN, transition="instant",
                                       placement=policy,
                                       migrate_on_departure=False)
            ref = CapacityBroker.build(4, GN, transition="instant",
                                       placement=lambda b, t, _fn=fn: _fn(b, t),
                                       migrate_on_departure=False)
            pool = _pool(5)
            for i in range(24):
                t = dataclasses.replace(pool[i % len(pool)], name=f"s{i}")
                dv, dr = vec.admit(t), ref.admit(t)
                assert dv.admitted == dr.admitted
                assert dv.host == dr.host, (policy, i)

    if HAVE_HYPOTHESIS:
        @settings(max_examples=30, deadline=None,
                  suppress_health_check=[HealthCheck.too_slow])
        @given(seed=st.integers(0, 10_000), n_hosts=st.integers(2, 24),
               drain=st.integers(0, 3))
        def test_property_all_policies(self, seed, n_hosts, drain):
            _assert_orders_identical(_random_fleet(seed, n_hosts, drain))
    else:
        def test_property_all_policies(self):
            pytest.skip("property test needs hypothesis")


# ---------------------------------------------------------------------------
# incremental accounting: slice ledger counter, broker free arrays, memo
# ---------------------------------------------------------------------------


class TestIncrementalAccounting:
    def test_slice_pool_counter_tracks_recompute(self, monkeypatch):
        # force the debug cross-check on: every capacity_in_use read
        # asserts counter == recomputed sum
        monkeypatch.setattr(capacity_mod, "_DEBUG", True)
        ctl = DynamicController(GN, transition="instant")
        names = []
        for i, t in enumerate(_pool(9, n=6)):
            t = dataclasses.replace(t, name=f"n{i}")
            if ctl.admit(t).admitted:
                names.append(t.name)
            assert ctl.pool.capacity_in_use == sum(
                e.gn_hi for e in ctl.pool.entries()
            )
        for n in names[::2]:
            ctl.release(n)
            ctl.pool.capacity_in_use

    def test_fork_adopt_preserve_counter(self, monkeypatch):
        monkeypatch.setattr(capacity_mod, "_DEBUG", True)
        pool = SlicePool(GN)
        pool.reserve(capacity_mod.Entry(task=_task(11, 0.05, "x"), alloc=2))
        child = pool.fork()
        child.reserve(capacity_mod.Entry(task=_task(12, 0.05, "y"), alloc=3))
        assert child.capacity_in_use == 5
        pool.adopt(child)
        assert pool.capacity_in_use == 5
        pool.set_alloc("x", 4)
        assert pool.capacity_in_use == 7
        pool.reclaim("y")
        assert pool.capacity_in_use == 4

    def test_broker_free_array_exact_under_direct_host_admits(self):
        """Capacity listeners: admitting directly on a host controller
        (bypassing the broker) must still update the broker's free
        array — the arrays are never recomputed from residents."""
        broker = CapacityBroker.build(3, GN, transition="instant",
                                      migrate_on_departure=False)
        assert broker.hosts[1].admit(_task(13, 0.05, "direct")).admitted
        for h, ctl in enumerate(broker.hosts):
            assert broker._free[h] == ctl.free_capacity
        broker.hosts[1].release("direct")
        for h, ctl in enumerate(broker.hosts):
            assert broker._free[h] == ctl.free_capacity

    def test_memo_overlay_copy_on_write(self):
        base = {("a",): 1.0, ("b",): 2.0}
        ov = MemoOverlay(base)
        assert ov.get(("a",)) == 1.0           # falls through
        ov[("a",)] = 9.0
        ov[("c",)] = 3.0
        assert ov.get(("a",)) == 9.0           # local wins
        assert ov.get(("c",)) == 3.0
        assert base == {("a",): 1.0, ("b",): 2.0}   # base untouched
        ov.flush_into(base)
        assert base == {("a",): 9.0, ("b",): 2.0, ("c",): 3.0}


# ---------------------------------------------------------------------------
# elastic fleets: runtime join, certified drain-then-retire
# ---------------------------------------------------------------------------


class TestElasticFleet:
    def _broker(self, n=3):
        return CapacityBroker.build(n, GN, transition="instant",
                                    placement="least_loaded")

    def test_add_host_is_immediately_placeable(self):
        broker = self._broker()
        # fill existing hosts enough that least_loaded prefers the joiner
        for i in range(6):
            assert broker.admit(_task(20 + i, 0.05, f"t{i}")).admitted
        h = broker.add_host(gn_total=GN, speed=1.0)
        assert h == 3 and broker.n_hosts == 4
        dec = broker.admit(_task(30, 0.05, "late"))
        assert dec.admitted and dec.host == h
        assert broker._free[h] == broker.hosts[h].free_capacity

    def test_retire_drains_via_certified_migrations(self):
        broker = self._broker()
        for i in range(6):
            assert broker.admit(_task(40 + i, 0.05, f"t{i}")).admitted
        resident_on_0 = [n for n, h in broker._active.items() if h == 0]
        assert resident_on_0
        assert broker.retire_host(0)
        assert 0 in broker.retired          # instant mode: drains inline
        for n in resident_on_0:
            h = broker.active_host(n)
            assert h is not None and h != 0
            assert broker.bound(n) != np.inf
        # retired host excluded from placement and capacity totals
        assert 0 not in broker.active_host_indices
        for _ in range(20):
            dec = broker.admit(_task(60, 0.05, f"x{_}"))
            if not dec.admitted:
                break
            assert dec.host != 0

    def test_retire_guards(self):
        broker = self._broker(2)
        assert broker.retire_host(0)
        assert broker.retire_host(1) is False      # never drain last host
        assert broker.retire_host(0) is False      # already retired
        with pytest.raises(IndexError):
            broker.retire_host(5)

    def test_failed_drain_rolls_back_draining_flag(self):
        # 2 hosts, host 1 nearly full: draining host 0 cannot place its
        # residents, retire must refuse and leave host 0 active
        broker = self._broker(2)
        for i in range(20):
            if not broker.admit(_task(70 + i, 0.1, f"t{i}")).admitted:
                break
        if broker.free_capacity == 0:
            assert broker.retire_host(0) is False
            assert 0 not in broker.draining
            assert 0 in broker.active_host_indices

    def test_elastic_mid_churn_simulation(self):
        """Join a host mid-churn, then drain a host with jobs in flight:
        zero deadline misses, zero analytic-bound violations."""
        events = []
        for i in range(8):
            t = _task(80 + i, 0.35, f"svc{i}")
            events.append(ChurnEvent(time=float(i), kind="admit",
                                     name=t.name, task=t))
        events.append(ChurnEvent(time=30.0, kind="release",
                                 name="svc1", task=None))
        res = simulate_fleet(
            events, n_hosts=3, gn_per_host=GN, horizon=150.0, seed=7,
            elastic=[(20.0, "add", GN, 1.25), (40.0, "retire", 0)],
        )
        assert [e["ok"] for e in res.fleet_events] == [True, True]
        assert res.n_hosts == 4
        assert sum(res.misses.values()) == 0
        assert res.bound_violations() == []
        # the drain actually moved someone off host 0
        assert any(m["src"] == 0 for m in res.migrations)


# ---------------------------------------------------------------------------
# hierarchical broker sharding
# ---------------------------------------------------------------------------


class TestBrokerTree:
    def test_admit_locate_release_roundtrip(self):
        tree = BrokerTree.build(16, GN, hosts_per_shard=4, fanout=2,
                                transition="instant",
                                migrate_on_departure=False)
        pool = _pool(6)
        names = []
        for i in range(40):
            t = dataclasses.replace(pool[i % len(pool)], name=f"t{i}")
            dec = tree.admit(t)
            assert dec.admitted, dec.reason
            names.append(t.name)
        assert tree.residents == 40
        assert tree.n_hosts == 16
        for n in names:
            leaf, h = tree.locate(n)
            assert leaf.task(n) is not None
            assert tree.bound(n) == leaf.bound(n) != np.inf
        assert tree.admit(dataclasses.replace(pool[0],
                                              name=names[0])).admitted \
            is False                      # fleet-unique names
        for n in names:
            assert tree.release(n)
        assert tree.residents == 0 and tree.capacity_in_use == 0

    @pytest.mark.parametrize("level", [100, 1000])
    def test_fill_with_headroom_admits_every_arrival(self, level):
        """A tree provisioned with 30 % more slices than ``level`` pool
        services take on a scratch host admits every one of them."""
        pool = _pool(3, n=16)
        probe = DynamicController(GN, transition="instant")
        allocs = []
        for t in pool:
            if probe.admit(t).admitted:
                allocs.append(probe.allocation[t.name])
                probe.release(t.name)
        n_hosts = int(np.ceil(level * np.mean(allocs) / GN * 1.3))
        tree = BrokerTree.build(n_hosts, GN, transition="instant",
                                engine="batch", migrate_on_departure=False)
        for i in range(level):
            dec = tree.admit(dataclasses.replace(pool[i % len(pool)],
                                                 name=f"svc{i}"))
            assert dec.admitted, (i, dec.reason)
        assert tree.residents == level

    def test_digest_prunes_implausible_shards(self):
        tree = BrokerTree.build(8, GN, hosts_per_shard=4, fanout=4,
                                transition="instant",
                                migrate_on_departure=False)
        assert len(tree.children) == 2
        # fill shard 0 completely so its digest cannot fit any arrival
        pool = _pool(6)
        i = 0
        while tree.children[0].free_capacity > 0:
            t = dataclasses.replace(pool[i % len(pool)], name=f"fill{i}")
            assert tree.children[0].admit(t).admitted
            i += 1
        calls = []
        for ci, child in enumerate(tree.children):
            orig = child.admit

            def wrap(task, *a, _ci=ci, _orig=orig, **kw):
                calls.append(_ci)
                return _orig(task, *a, **kw)

            child.admit = wrap
        dec = tree.admit(_task(90, 0.05, "probe"), allow_realloc=False)
        assert dec.admitted
        assert calls == [1], "full shard was descended despite digest"

    def test_shard_descent_metrics(self):
        reg = metrics.enable(fresh=True)
        tree = BrokerTree.build(8, GN, hosts_per_shard=4, fanout=4,
                                transition="instant",
                                migrate_on_departure=False)
        assert tree.admit(_task(91, 0.05, "m")).admitted
        snap = reg.snapshot()
        assert "broker_shard_descents_total" in snap
        assert sum(
            snap["broker_shard_descents_total"]["series"].values()
        ) >= 1

    def test_parity_with_flat_broker(self):
        """Every admission a flat broker accepts, the same hosts sharded
        into a tree accept too (the tree only prunes shards that cannot
        fit — it never rejects a placeable arrival)."""
        flat = CapacityBroker.build(8, GN, transition="instant",
                                    migrate_on_departure=False)
        tree = BrokerTree.build(8, GN, hosts_per_shard=4, fanout=4,
                                transition="instant",
                                migrate_on_departure=False)
        pool = _pool(8)
        for i in range(30):
            t = dataclasses.replace(pool[i % len(pool)], name=f"p{i}")
            df, dt_ = flat.admit(t), tree.admit(t)
            if df.admitted:
                assert dt_.admitted, (i, dt_.reason)
        assert tree.free_capacity == flat.free_capacity

    def test_infeasible_arrival_rejected_without_descent(self):
        tree = BrokerTree.build(4, 2, hosts_per_shard=2, fanout=2,
                                transition="instant",
                                migrate_on_departure=False)
        # a heavy task whose minimum span cannot meet its deadline on
        # any host this small: g_min screen rejects at the root
        heavy = _task(92, 3.5, "heavy")
        dec = tree.admit(heavy)
        assert not dec.admitted
        assert "digest" in dec.reason


# ---------------------------------------------------------------------------
# journaled elastic recovery
# ---------------------------------------------------------------------------


class TestElasticRecovery:
    def test_journal_roundtrip_add_and_retire(self, tmp_path):
        path = str(tmp_path / "fleet.db")
        j = Journal(path)
        broker = CapacityBroker.build(3, GN, transition="instant",
                                      journal=j)
        for i in range(5):
            assert broker.admit(_task(95 + i, 0.05, f"t{i}"),
                                t=float(i)).admitted
        broker.add_host(gn_total=GN, speed=1.5, t=5.0)
        assert broker.admit(_task(99, 0.05, "late"), t=6.0).admitted
        assert broker.retire_host(0, t=7.0)
        assert 0 in broker.retired
        snap_live = serialize_state(broker)
        j.close()

        j2 = Journal(path)
        b2, report = recover_broker(j2)
        assert not report.alerts
        assert b2.n_hosts == 4 and b2.retired == {0}
        assert b2.speeds == broker.speeds
        assert serialize_state(b2) == snap_live
        j2.close()

    def test_fleet_ops_survive_checkpoint(self, tmp_path):
        path = str(tmp_path / "fleet.db")
        j = Journal(path)
        broker = CapacityBroker.build(2, GN, transition="instant",
                                      journal=j)
        broker.add_host(gn_total=GN, t=1.0)
        assert broker.admit(_task(101, 0.05, "a"), t=2.0).admitted
        j.checkpoint(serialize_state(broker))
        assert broker.admit(_task(102, 0.05, "b"), t=3.0).admitted
        snap_live = serialize_state(broker)
        j.close()

        j2 = Journal(path)
        b2, _ = recover_broker(j2)
        assert b2.n_hosts == 3
        assert serialize_state(b2) == snap_live
        j2.close()

    def test_static_fleet_snapshot_schema_unchanged(self):
        broker = CapacityBroker.build(2, GN, transition="instant")
        assert broker.admit(_task(103, 0.05, "a")).admitted
        assert "fleet_ops" not in serialize_state(broker)
