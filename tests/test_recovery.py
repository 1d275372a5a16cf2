"""Durable control plane: journal, crash recovery, daemon, and the
exception-safety satellite.

The load-bearing property (ISSUE acceptance): for EVERY journal record
boundary across a scripted admit → update → migrate → release churn, a
simulated crash (replay of the prefix) rebuilds a `SlicePool` and
certified-bound set bit-identical to the uncrashed oracle at that point,
with mid-migration crashes resolved deterministically to a deadline-safe
side (forward iff the target's admit record committed, back otherwise).
"""
import dataclasses
import json
import math
import os
import signal
import socket
import sqlite3
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from repro.core import GeneratorConfig, generate_taskset
from repro.obs import metrics
from repro.obs.monitor import BoundMonitor
from repro.sched import (
    CapacityBroker,
    DynamicController,
    EventTrace,
    Journal,
    SlicePool,
    recover,
    recover_broker,
    recover_controller,
    replay,
    serialize_state,
)
from repro.sched.journal import (
    entry_from_dict,
    entry_to_dict,
    task_from_dict,
    task_to_dict,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tasks(seed=0, util=0.5, n=6, m=3):
    rng = np.random.default_rng(seed)
    return list(generate_taskset(
        rng, util, GeneratorConfig(n_tasks=n, n_subtasks=m)
    ))


def _task(seed, util, name):
    t = _tasks(seed=seed, util=util, n=1)[0]
    return dataclasses.replace(t, name=name)


def _small_task(seed, util, name):
    """One service of two subtasks: the shape of a resident in the large
    pools below."""
    t = generate_taskset(np.random.default_rng(seed), util,
                         GeneratorConfig(n_tasks=1, n_subtasks=2))[0]
    return dataclasses.replace(t, name=name)


def _pool_fp(entries):
    """SlicePool.fingerprint over recovered HostState entries."""
    pool = SlicePool(0)
    for e in entries.values():
        pool.reserve(e.copy())
    return pool.fingerprint()


def _ctl_snap(ctl):
    return (ctl.pool.fingerprint(), tuple(sorted(ctl.bounds().items())),
            ctl.epoch)


def _host_snap(state, h):
    st = state.hosts.get(h)
    if st is None:
        return (SlicePool(0).fingerprint(), (), 0)
    return (_pool_fp(st.entries), tuple(sorted(st.bounds.items())), st.epoch)


# ---- journal mechanics -------------------------------------------------------

class TestJournal:
    def test_monotonic_seq_and_payload_roundtrip(self):
        j = Journal(":memory:")
        s1 = j.append("admit", "a", t=1.0, gn=3, bounds={"a": 1.25})
        s2 = j.append("release", "a", t=2.0, epoch=2)
        assert s2 == s1 + 1 == 2
        recs = j.records()
        assert [r.op for r in recs] == ["admit", "release"]
        assert recs[0].payload == {"gn": 3, "bounds": {"a": 1.25}}
        assert recs[0].t == 1.0 and recs[0].host is None
        assert j.records(up_to=s1) == recs[:1]

    def test_seq_survives_compaction(self):
        j = Journal(":memory:")
        for i in range(5):
            j.append("admit", f"t{i}")
        covered = j.checkpoint({"format": 1, "hosts": {}, "active": {},
                                "migrations": {}})
        assert covered == 5 and j.records() == []
        assert j.append("admit", "t5") == 6        # AUTOINCREMENT: no reuse
        assert j.last_seq == 6
        assert j.snapshot()[0] == 5

    def test_meta_mismatch_rejected(self):
        j = Journal(":memory:")
        j.ensure_meta("host0", {"gn_total": 8})
        j.ensure_meta("host0", {"gn_total": 8})    # idempotent
        with pytest.raises(ValueError, match="differently-configured"):
            j.ensure_meta("host0", {"gn_total": 16})

    def test_task_and_entry_serialization_bit_exact(self):
        for t in _tasks(seed=3, n=4):
            back = task_from_dict(json.loads(json.dumps(task_to_dict(t))))
            assert back == t                        # floats round-trip exactly
        from repro.sched import Entry
        e = Entry(task=_task(1, 0.1, "x"), alloc=3, departing=True)
        e.staged_task = dataclasses.replace(e.task, period=e.task.period * 2)
        back = entry_from_dict(json.loads(json.dumps(entry_to_dict(e))))
        assert (back.task, back.alloc, back.staged_task, back.staged_alloc,
                back.departing) == (e.task, e.alloc, e.staged_task,
                                    e.staged_alloc, e.departing)

    def test_journal_does_not_change_decisions(self, tmp_path):
        """The same twelve arrivals, with and without a journal attached:
        every one admitted, at the same allocation and bounds."""
        decisions = []
        for journal in (None, Journal(str(tmp_path / "j.sqlite"))):
            ctl = DynamicController(32, transition="instant",
                                    journal=journal)
            decisions.append([
                (d.admitted, d.alloc, d.bounds) for d in (
                    ctl.admit(_small_task(7 + i, 0.02, f"t{i}"))
                    for i in range(12)
                )
            ])
            if journal is not None:
                journal.close()
        assert all(admitted for admitted, _, _ in decisions[0])
        assert decisions[0] == decisions[1]

    def test_journal_metrics_emitted(self, tmp_path):
        metrics.enable(fresh=True)
        try:
            j = Journal(str(tmp_path / "j.sqlite"))
            j.append("admit", "a")
            j.checkpoint({"format": 1, "hosts": {}, "active": {},
                          "migrations": {}})
            snap = metrics.registry().snapshot()
            assert snap["journal_records_total"]["series"]["op=admit"] == 1.0
            assert snap["journal_fsync_seconds"]["series"][""]["count"] == 1
            assert snap["journal_checkpoints_total"]["series"][""] == 1.0
        finally:
            metrics.disable()


# ---- single-host crash matrix ------------------------------------------------

def _run_script(ctl, ops):
    """Apply ops; return {last_seq_after_op: oracle snapshot} (seq 0 = the
    pre-script empty state)."""
    oracle = {ctl.journal.last_seq: _ctl_snap(ctl)}
    for kind, args in ops:
        if kind == "admit":
            assert ctl.admit(*args).admitted
        elif kind == "update":
            assert ctl.update_rate(*args).admitted
        elif kind == "release":
            assert ctl.release(*args)
        elif kind == "boundary":
            assert ctl.job_boundary(*args) != "none"
        oracle[ctl.journal.last_seq] = _ctl_snap(ctl)
    return oracle


class TestCrashMatrixSingleHost:
    def _assert_matrix(self, j, oracle, gn_total):
        """Every record boundary replays to the oracle state at the
        largest op boundary <= k (single-host ops are one record each, so
        every k IS an op boundary)."""
        assert set(range(j.last_seq + 1)) == set(oracle), \
            "every record must be one op boundary"
        for k in range(j.last_seq + 1):
            state = replay(j, up_to=k)
            assert _host_snap(state, 0) == oracle[k], f"crash after seq {k}"

    def test_instant_mode_every_boundary(self):
        j = Journal(":memory:")
        ctl = DynamicController(10, transition="instant", journal=j)
        names = [_task(i, 0.06, f"t{i}") for i in range(5)]
        ops = [("admit", (names[0],)), ("admit", (names[1],)),
               ("admit", (names[2],)),
               ("update", ("t1", names[1].period * 1.5,
                           names[1].deadline * 1.5)),
               ("release", ("t0",)),
               ("admit", (names[3],)),
               ("release", ("t2",)),
               ("admit", (names[4],))]
        oracle = _run_script(ctl, ops)
        self._assert_matrix(j, oracle, 10)
        # full recovery rebuilds a live controller bit-identically
        ctl2, report = recover_controller(j)
        assert _ctl_snap(ctl2) == _ctl_snap(ctl)
        assert not report.alerts
        assert all(c in ("exact", "conservative")
                   for c in report.recert.get(0, {}).values())

    def test_boundary_mode_every_boundary(self):
        j = Journal(":memory:")
        ctl = DynamicController(10, transition="boundary", journal=j)
        names = [_task(10 + i, 0.06, f"b{i}") for i in range(4)]
        ops = [("admit", (names[0],)), ("admit", (names[1],)),
               ("admit", (names[2],)),
               ("update", ("b1", names[1].period * 1.4,
                           names[1].deadline * 1.4)),    # staged
               ("release", ("b0",)),                     # depart mark
               ("boundary", ("b1",)),                    # commit the stage
               ("boundary", ("b0",)),                    # reclaim departer
               ("admit", (names[3],))]
        oracle = _run_script(ctl, ops)
        self._assert_matrix(j, oracle, 10)
        ctl2, report = recover_controller(j)
        assert _ctl_snap(ctl2) == _ctl_snap(ctl)
        assert not report.alerts

    def test_recovered_controller_keeps_journaling(self):
        j = Journal(":memory:")
        ctl = DynamicController(8, transition="instant", journal=j)
        assert ctl.admit(_task(0, 0.1, "a")).admitted
        ctl2, _ = recover_controller(j)
        assert ctl2.admit(_task(1, 0.1, "b")).admitted
        ctl3, _ = recover_controller(j)
        assert _ctl_snap(ctl3) == _ctl_snap(ctl2)
        assert sorted(ctl3.allocation) == ["a", "b"]

    def test_compaction_preserves_recovery(self):
        j = Journal(":memory:")
        ctl = DynamicController(8, transition="instant", journal=j)
        assert ctl.admit(_task(0, 0.08, "a")).admitted
        assert ctl.admit(_task(1, 0.08, "b")).admitted
        j.checkpoint(serialize_state(ctl))
        assert ctl.release("a")
        assert ctl.admit(_task(2, 0.08, "c")).admitted
        ctl2, report = recover_controller(j)
        assert report.state.from_snapshot
        assert report.state.replayed == 2           # only the suffix
        assert _ctl_snap(ctl2) == _ctl_snap(ctl)

    def test_replay_before_snapshot_is_an_error(self):
        j = Journal(":memory:")
        ctl = DynamicController(8, transition="instant", journal=j)
        assert ctl.admit(_task(0, 0.08, "a")).admitted
        j.checkpoint(serialize_state(ctl))
        with pytest.raises(ValueError, match="compacted"):
            replay(j, up_to=0)


# ---- fleet crash matrix (two-phase migration) --------------------------------

def _mk_fleet(j, transition):
    return CapacityBroker.build(
        2, 12, transition=transition, journal=j, placement="first_fit",
        migrate_on_departure=False, imbalance_threshold=0.1,
    )


def _fleet_snap(br):
    return (
        tuple(_ctl_snap(ctl) for ctl in br.hosts),
        tuple(sorted(br._active.items())),
        tuple(sorted(br.migrating.items())),
    )


def _recovered_fleet_snap(state, n_hosts):
    return (
        tuple(_host_snap(state, h) for h in range(n_hosts)),
        tuple(sorted(state.active.items())),
        tuple(sorted(state.migrations.items())),
    )


class TestCrashMatrixFleet:
    def test_boundary_migration_every_record_boundary(self):
        j = Journal(":memory:")
        br = _mk_fleet(j, "boundary")
        for i in range(4):
            assert br.admit(_task(i, 0.05, f"t{i}")).admitted
        before_mig = _fleet_snap(br)
        seq_before = j.last_seq
        assert br.rebalance(t=5.0) == 1
        after_mig = _fleet_snap(br)
        (name, mig), = br.migrating.items()

        mig_recs = [r for r in j.records() if r.seq > seq_before]
        ops = [(r.op, r.phase) for r in mig_recs]
        assert ops == [("migrate", "intent"), ("admit", "commit"),
                       ("depart", "commit"), ("migrate", "commit")]
        intent_seq = mig_recs[0].seq

        # complete the move at the source job boundary
        assert br.job_boundary(name, t=6.0) == "migrated"
        done = _fleet_snap(br)

        for k in range(j.last_seq + 1):
            state = replay(j, up_to=k)
            got = _recovered_fleet_snap(state, 2)
            if k < intent_seq:
                # pre-migration prefix: plain per-host ops (each its own
                # boundary — covered exhaustively by the single-host matrix)
                continue
            if k == intent_seq:
                assert got == before_mig, "intent alone must roll back"
                assert state.rolled_back == [name]
            elif k < j.last_seq:
                # target admit is durable: roll forward to the full
                # post-migration state, whichever side the crash hit
                assert got == after_mig, f"crash after seq {k}"
                assert (state.rolled_forward == [name]
                        or (k == j.last_seq - 1 and not state.rolled_forward)
                        or state.rolled_forward == [name])
            else:
                assert got == done
        # and the final state recovers into a live broker bit-identically
        br2, report = recover_broker(j)
        assert _fleet_snap(br2) == done
        assert not report.alerts

    def test_instant_migration_completes_immediately(self):
        j = Journal(":memory:")
        br = _mk_fleet(j, "instant")
        for i in range(4):
            assert br.admit(_task(i, 0.05, f"i{i}")).admitted
        assert br.rebalance(t=3.0) == 1
        assert not br.migrating                    # instant source: done
        done = _fleet_snap(br)
        recs = j.records()
        commit = [r for r in recs if r.op == "migrate"][-1]
        assert commit.payload["completed"] is True
        # crash between source release and broker commit: rolls forward
        state = replay(j, up_to=commit.seq - 1)
        assert _recovered_fleet_snap(state, 2) == done
        assert state.rolled_forward == [commit.task]
        br2, report = recover_broker(j)
        assert _fleet_snap(br2) == done
        assert not report.alerts

    def test_mid_migration_fleet_release_abort(self):
        j = Journal(":memory:")
        br = _mk_fleet(j, "boundary")
        for i in range(4):
            assert br.admit(_task(i, 0.05, f"r{i}")).admitted
        assert br.rebalance(t=2.0) == 1
        (name, _), = br.migrating.items()
        assert br.release(name, t=3.0)             # departs BOTH sides
        assert br.job_boundary(name, t=4.0) == "reclaimed"
        done = _fleet_snap(br)
        br2, report = recover_broker(j)
        assert _fleet_snap(br2) == done
        assert name not in br2._active and name not in br2.migrating

    def test_rejected_target_rolls_back(self):
        j = Journal(":memory:")
        br = _mk_fleet(j, "boundary")
        for i in range(4):
            assert br.admit(_task(i, 0.05, f"x{i}")).admitted
        # an abort record with no following admit must leave no trace
        j.append("migrate", "x0", t=9.0, phase="intent", src=0, dst=1)
        j.append("migrate", "x0", t=9.0, phase="abort", src=0, dst=1,
                 reason="target rejected")
        state = replay(j)
        assert _recovered_fleet_snap(state, 2) == _fleet_snap(br)
        assert not state.rolled_forward and not state.rolled_back


# ---- re-certification & quarantine -------------------------------------------

class TestRecertification:
    def test_clean_journal_recertifies_exact(self):
        j = Journal(":memory:")
        ctl = DynamicController(10, transition="instant", journal=j)
        for i in range(3):
            assert ctl.admit(_task(i, 0.06, f"t{i}")).admitted
        report = recover(j)
        assert report.recert[0] == {"t0": "exact", "t1": "exact",
                                    "t2": "exact"}
        assert not report.alerts

    def test_stale_superset_bounds_are_conservative_not_quarantined(self):
        j = Journal(":memory:")
        ctl = DynamicController(10, transition="instant", journal=j)
        for i in range(3):
            assert ctl.admit(_task(i, 0.06, f"t{i}")).admitted
        assert ctl.release("t1")   # remaining bounds now a stale superset
        report = recover(j)
        assert not report.alerts
        assert set(report.recert[0].values()) <= {"exact", "conservative"}
        # recovered bounds equal the live (journaled) ones bit-exactly
        st = report.state.hosts[0]
        assert st.bounds == ctl.bounds()

    def test_tampered_bound_is_quarantined_with_alert(self):
        j = Journal(":memory:")
        ctl = DynamicController(10, transition="instant", journal=j)
        for i in range(2):
            assert ctl.admit(_task(i, 0.06, f"t{i}")).admitted
        # tamper: rewrite the last admit's certified bounds to a value the
        # analysis cannot reproduce (far below any feasible response)
        recs = j.records()
        last = recs[-1]
        payload = dict(last.payload)
        payload["bounds"] = {k: 1e-9 for k in payload["bounds"]}
        j._conn.execute(
            "UPDATE journal SET payload = ? WHERE seq = ?",
            (json.dumps(payload, sort_keys=True, separators=(",", ":")),
             last.seq),
        )
        report = recover(j)
        quarantined = [name for name, c in report.recert[0].items()
                       if c == "quarantined"]
        assert quarantined, "tampered bounds must be caught"
        assert {a.task for a in report.alerts} == set(quarantined)
        for a in report.alerts:
            assert a.kind == "recertification_mismatch"
            assert a.recomputed > a.journaled
            assert a.action == "quarantined"
        for name in quarantined:                   # removed, not re-trusted
            assert name not in report.state.hosts[0].entries
            assert name not in report.state.hosts[0].bounds

    def test_preemptive_arbitration_recovers_bit_exact(self):
        j = Journal(":memory:")
        ctl = DynamicController(6, transition="instant", journal=j,
                                preemption="priority",
                                gpu_ctx_overhead=0.01)
        for i in range(3):
            assert ctl.admit(_task(20 + i, 0.05, f"p{i}")).admitted
        ctl2, report = recover_controller(j)
        assert _ctl_snap(ctl2) == _ctl_snap(ctl)
        assert ctl2.preemption.enabled and ctl2.preemption.ctx == 0.01
        assert not report.alerts
        assert set(report.recert[0].values()) == {"exact"}

    def test_resident_pool_recovers_exact(self, tmp_path):
        """A 30-resident pool admitted through a journaled controller
        (128 slices, pinned path only) comes back from a cold reopen of
        the journal with the same residents and bounds, none
        quarantined."""
        path = str(tmp_path / "pool.sqlite")
        j = Journal(path)
        ctl = DynamicController(128, transition="instant", journal=j,
                                allow_realloc=False, max_candidates=16)
        for i in range(30):
            dec = ctl.admit(_small_task(7 + i, 0.004, f"r{i}"))
            assert dec.admitted, (i, dec.reason)
        j.close()                                  # the process dies here
        cold = Journal(path)
        ctl2, report = recover_controller(cold)
        cold.close()
        assert not report.quarantined
        assert sorted(ctl2.allocation) == sorted(ctl.allocation)
        assert ctl2.bounds() == ctl.bounds()

    def test_config_drift_rejected_on_reattach(self):
        j = Journal(":memory:")
        DynamicController(8, transition="instant", journal=j)
        with pytest.raises(ValueError, match="differently-configured"):
            DynamicController(16, transition="instant", journal=j)


# ---- recovery properties -----------------------------------------------------

def _churn_script(seed):
    """Deterministic mixed script from a seed; returns (journal, oracle
    controller, released names)."""
    rng = np.random.default_rng(seed)
    j = Journal(":memory:")
    ctl = DynamicController(10, transition="instant", journal=j)
    released = []
    i = 0
    for _ in range(12):
        resident = sorted(ctl.allocation)
        op = rng.integers(0, 3)
        if op == 0 or not resident:
            ctl.admit(_task(int(rng.integers(0, 1000)), 0.05, f"s{i}"))
            i += 1
        elif op == 1:
            name = resident[int(rng.integers(0, len(resident)))]
            if ctl.release(name):
                released.append(name)
        else:
            name = resident[int(rng.integers(0, len(resident)))]
            t = ctl.task(name)
            ctl.update_rate(name, t.period * 1.25, t.deadline * 1.25)
    return j, ctl, released


class TestRecoveryProperties:
    @pytest.mark.parametrize("seed", range(5))
    def test_idempotent_no_resurrection_no_drop(self, seed):
        j, ctl, released = _churn_script(seed)
        for k in range(j.last_seq + 1):
            s1 = replay(j, up_to=k)
            s2 = replay(j, up_to=k)            # idempotent: pure read
            assert (_recovered_fleet_snap(s1, 1)
                    == _recovered_fleet_snap(s2, 1))
        final = replay(j)
        recovered = set(final.hosts.get(0).entries if final.hosts else ())
        resident = set(ctl.allocation)
        assert recovered == resident           # never drops a certified task
        for name in set(released) - resident:  # never resurrects a release
            assert name not in recovered

    def test_hypothesis_property(self):
        hypothesis = pytest.importorskip(
            "hypothesis", reason="property tests need hypothesis"
        )
        from hypothesis import given, settings
        from hypothesis import strategies as st

        @settings(max_examples=15, deadline=None)
        @given(seed=st.integers(min_value=0, max_value=10_000))
        def run(seed):
            j, ctl, released = _churn_script(seed)
            s1 = replay(j)
            s2 = replay(j)
            assert (_recovered_fleet_snap(s1, 1)
                    == _recovered_fleet_snap(s2, 1))
            recovered = set(s1.hosts[0].entries) if s1.hosts else set()
            assert recovered == set(ctl.allocation)
            assert not (recovered & (set(released) - set(ctl.allocation)))

        run()


# ---- exception-safe subscriber callbacks (satellite) -------------------------

class TestCallbackSafety:
    def test_raising_trace_subscriber_does_not_abort_or_starve(self):
        trace = EventTrace()
        seen = []
        trace.attach(lambda ev: (_ for _ in ()).throw(RuntimeError("boom")))
        trace.attach(lambda ev: seen.append(ev.kind))
        metrics.enable(fresh=True)
        try:
            ev = trace.record(1.0, "admit", "a", gn=2)
            assert metrics.registry().value(
                "monitor_callback_errors_total") == 1.0
        finally:
            metrics.disable()
        assert ev in trace.events                  # the record still landed
        assert seen == ["admit"]                   # later subscribers ran

    def test_raising_on_alert_does_not_abort_monitor(self):
        calls = []

        def bad(alert):
            calls.append(alert.kind)
            raise ValueError("subscriber bug")

        mon = BoundMonitor(on_alert=bad)
        ev = type("Ev", (), {"t": 1.0, "kind": "miss", "task": "a",
                             "meta": {"overshoot": 0.5}})()
        metrics.enable(fresh=True)
        try:
            mon.observe_event(ev)                  # must not raise
            assert metrics.registry().value(
                "monitor_callback_errors_total") == 1.0
        finally:
            metrics.disable()
        assert calls == ["deadline_miss"]
        assert [a.kind for a in mon.alerts] == ["deadline_miss"]

    def test_controller_commit_survives_raising_subscriber(self):
        trace = EventTrace()
        trace.attach(lambda ev: (_ for _ in ()).throw(RuntimeError("boom")))
        ctl = DynamicController(8, transition="instant", trace=trace)
        dec = ctl.admit(_task(0, 0.1, "a"))        # records through the trace
        assert dec.admitted and "a" in ctl.allocation


# ---- daemon ------------------------------------------------------------------

def _specs(n, util=0.06):
    return [task_to_dict(_task(i, util, f"d{i}")) for i in range(n)]


class TestDaemonInProcess:
    def _daemon(self, tmp_path, **kw):
        from repro.sched.daemon import SchedulerDaemon
        return SchedulerDaemon(
            str(tmp_path / "j.sqlite"), str(tmp_path / "s.sock"),
            gn_total=10, **kw,
        )

    def test_submit_status_cancel(self, tmp_path):
        d = self._daemon(tmp_path)
        for spec in _specs(3):
            resp = d.handle({"cmd": "submit", "task": spec})
            assert resp["ok"] and resp["admitted"], resp
        st = d.status()
        assert sorted(st["resident"]) == ["d0", "d1", "d2"]
        assert all(math.isfinite(b) for b in st["bounds"].values())
        assert d.handle({"cmd": "cancel", "name": "d1"})["released"]
        assert sorted(d.status()["resident"]) == ["d0", "d2"]
        assert not d.handle({"cmd": "cancel", "name": "nope"})["released"]

    def test_kill_and_recover_in_process(self, tmp_path):
        d = self._daemon(tmp_path)
        for spec in _specs(3):
            assert d.handle({"cmd": "submit", "task": spec})["admitted"]
        before = d.status()
        d.journal.close()                          # simulated hard kill
        d2 = self._daemon(tmp_path)
        assert d2.recovered
        after = d2.status()
        assert after["resident"] == before["resident"]
        assert after["bounds"] == before["bounds"]
        assert after["recovery"]["quarantined"] == []

    def test_auto_compaction_and_drain(self, tmp_path):
        d = self._daemon(tmp_path, compact_every=2)
        for spec in _specs(4):
            assert d.handle({"cmd": "submit", "task": spec})["admitted"]
        assert d.journal.snapshot() is not None    # compaction cadence hit
        resp = d.handle({"cmd": "drain"})
        assert resp["ok"] and sorted(resp["released"]) == \
            ["d0", "d1", "d2", "d3"]
        assert d.status()["resident"] == {}
        denied = d.handle({"cmd": "submit", "task": _specs(1)[0]})
        assert denied["ok"] and not denied["admitted"]
        d.journal.close()
        d2 = self._daemon(tmp_path)                # drained state recovers
        assert d2.status()["resident"] == {}

    def test_bad_request_is_an_error_not_a_crash(self, tmp_path):
        d = self._daemon(tmp_path)
        assert not d.handle({"cmd": "submit", "task": {"nope": 1}})["ok"]
        assert not d.handle({"cmd": "wat"})["ok"]
        assert d.handle({"cmd": "ping"})["ok"]     # loop still healthy


@pytest.mark.skipif(not hasattr(socket, "AF_UNIX"),
                    reason="unix sockets required")
class TestDaemonEndToEnd:
    def _spawn(self, sock, journal):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src") + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.sched.daemon", "serve",
             "--journal", journal, "--socket", sock, "--gn-total", "10"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        from repro.sched.daemon import request
        deadline = time.time() + 30.0
        while time.time() < deadline:
            if proc.poll() is not None:
                out, err = proc.communicate()
                raise RuntimeError(f"daemon died: {err.decode()}")
            try:
                if request(sock, {"cmd": "ping"}).get("ok"):
                    return proc
            except (OSError, ConnectionError):
                time.sleep(0.1)
        proc.kill()
        raise RuntimeError("daemon never came up")

    def test_submit_kill9_restart_identical_resident_set(self):
        from repro.sched.daemon import request
        work = tempfile.mkdtemp(prefix="rtgpud")
        sock = os.path.join(work, "s.sock")
        journal = os.path.join(work, "j.sqlite")
        proc = self._spawn(sock, journal)
        try:
            for spec in _specs(3):
                resp = request(sock, {"cmd": "submit", "task": spec})
                assert resp["ok"] and resp["admitted"], resp
            before = request(sock, {"cmd": "status"})
            assert sorted(before["resident"]) == ["d0", "d1", "d2"]
        finally:
            proc.kill()                            # SIGKILL: no checkpoint
            proc.wait(timeout=10)
        proc2 = self._spawn(sock, journal)
        try:
            after = request(sock, {"cmd": "status"})
            assert after["recovered"]
            assert after["resident"] == before["resident"]
            assert after["bounds"] == before["bounds"]
            assert after["recovery"]["quarantined"] == []
            stop = request(sock, {"cmd": "stop"})  # graceful: checkpoints
            assert stop["ok"]
            proc2.wait(timeout=10)
            assert proc2.returncode == 0
        finally:
            if proc2.poll() is None:
                proc2.kill()
                proc2.wait(timeout=10)
        # the graceful stop compacted the journal
        with Journal(journal) as j:
            assert j.snapshot() is not None
            assert j.records() == []
