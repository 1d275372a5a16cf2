"""Equivalence suite: batched frontier analyzer vs the scalar oracle.

The batched path (``repro.core.rta_batch``) must be *result-identical* to
the scalar reference (``repro.core.rta`` + ``grid_search_dfs``):

  * identical schedulable verdicts, allocations, and R̂ (≤ 1e-9 — the
    NumPy backend is in fact bit-exact and asserted as such) over random
    task sets, priority orders, and gn_total;
  * identical warm-start behavior (hint-ordered search);
  * byte-identical admission decision streams (allocations, certified
    bounds, reject reasons, event traces) from ``DynamicController``
    running ``engine="batch"`` vs ``engine="scalar"`` over the golden
    churn scenarios;
  * the optional JAX backend (``repro.core.backend``) agrees to ≤ 1e-9
    (exercised in a subprocess: selecting it flips the process-global
    ``jax_enable_x64`` flag, which must not leak into other tests).
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (
    GOLDEN_SCENARIOS,
    ChurnConfig,
    GeneratorConfig,
    TaskSet,
    available_backends,
    generate_churn_trace,
    generate_taskset,
)
from repro.core.federated import grid_search_dfs, iter_allocations, min_viable_alloc
from repro.core.rta import RtgpuIncremental
from repro.core.rta_batch import BatchAnalyzer, grid_search_frontier
from repro.sched import DynamicController, EventTrace

_TOL = 1e-9


def _taskset(seed: int, util: float, n: int = 4, m: int = 4,
             shuffle: bool = False) -> TaskSet:
    rng = np.random.default_rng(seed)
    ts = generate_taskset(
        rng, util, GeneratorConfig(n_tasks=n, n_subtasks=m, variability=0.2)
    )
    if shuffle:
        # non-deadline-monotonic priority order: the analysis is defined
        # for ANY fixed order, and the batch path must follow suit
        order = rng.permutation(len(ts))
        ts = TaskSet(tuple(ts.tasks[i] for i in order))
    return ts


def _assert_same_result(dfs, frontier, ctx=""):
    assert dfs.schedulable == frontier.schedulable, ctx
    assert dfs.alloc == frontier.alloc, ctx
    if dfs.schedulable:
        for a, b in zip(dfs.analysis.responses, frontier.analysis.responses):
            assert a == b, f"{ctx}: R̂ {a} != {b} (diff {a - b})"
        for ta, tb in zip(dfs.analysis.tasks, frontier.analysis.tasks):
            assert ta == tb, f"{ctx}: TaskAnalysis mismatch"


class TestSearchEquivalence:
    @pytest.mark.parametrize("seed", range(8))
    @pytest.mark.parametrize("util", [0.3, 0.7, 1.1])
    def test_frontier_matches_dfs(self, seed, util):
        ts = _taskset(seed, util)
        for gn_total in (6, 9):
            for tight in (False, True):
                d = grid_search_dfs(ts, gn_total, tightened=tight)
                f = grid_search_frontier(ts, gn_total, tightened=tight)
                _assert_same_result(d, f, f"seed={seed} u={util} gn={gn_total}")

    @pytest.mark.parametrize("seed", range(6))
    def test_shuffled_priority_orders(self, seed):
        ts = _taskset(seed, 0.6, shuffle=True)
        d = grid_search_dfs(ts, 8, tightened=True)
        f = grid_search_frontier(ts, 8, tightened=True)
        _assert_same_result(d, f, f"shuffled seed={seed}")

    @pytest.mark.parametrize("seed", range(6))
    def test_hint_order_matches_dfs(self, seed):
        """Warm-started search: hint-first visit order, same first success."""
        ts = _taskset(seed, 0.5, n=5)
        cold = grid_search_dfs(ts, 10, tightened=True)
        if not cold.schedulable:
            pytest.skip("unschedulable draw")
        hint = list(cold.alloc)
        hint[0] = None  # partial history
        d = grid_search_dfs(ts, 10, tightened=True, hint=hint)
        f = grid_search_frontier(ts, 10, tightened=True, hint=hint)
        _assert_same_result(d, f, f"hinted seed={seed}")


    @pytest.mark.parametrize("util", [0.3, 0.6, 0.9, 1.2, 1.6])
    def test_frontier_matches_dfs_under_node_budget(self, util):
        """Table-1 generator defaults (N=5, M=5) on 10 slices with a
        400-node budget: the two searches still agree."""
        ts = generate_taskset(np.random.default_rng(0), util,
                              GeneratorConfig())
        d = grid_search_dfs(ts, 10, tightened=True, max_nodes=400)
        f = grid_search_frontier(ts, 10, tightened=True, max_nodes=400)
        _assert_same_result(d, f, f"budget u={util}")


class TestAnalyzerEquivalence:
    @pytest.mark.parametrize("seed", range(6))
    def test_analyze_prefixes_bit_identical(self, seed):
        """Every per-candidate quantity matches analyze_task exactly."""
        ts = _taskset(seed, 0.8, n=5)
        mins = min_viable_alloc(ts, 10)
        if mins is None:
            pytest.skip("trivially infeasible draw")
        allocs = [a for _, a in zip(range(200), iter_allocations(mins, 10))]
        inc = RtgpuIncremental(ts, tightened=True)
        ba = BatchAnalyzer(ts, tightened=True)
        for k in range(len(ts)):
            prefixes = np.array([a[: k + 1] for a in allocs])
            da = ba.analyze_prefixes(k, prefixes)
            for i, a in enumerate(allocs):
                ta = inc.analyze_task(k, a[: k + 1])
                assert da.task_analysis(i) == ta, (seed, k, a)

    def test_bad_prefix_shape_rejected(self):
        ts = _taskset(0, 0.5)
        ba = BatchAnalyzer(ts)
        with pytest.raises(ValueError):
            ba.analyze_prefixes(2, np.ones((4, 2), dtype=np.int64))


class TestHypothesisEquivalence:
    """Randomized sweep over (seed, util, n, gn_total, tightened)."""

    @pytest.fixture(autouse=True)
    def _hyp(self):
        pytest.importorskip("hypothesis", reason="needs hypothesis")

    def test_randomized_equivalence(self):
        from hypothesis import HealthCheck, given, settings
        from hypothesis import strategies as st

        @settings(max_examples=25, deadline=None,
                  suppress_health_check=[HealthCheck.too_slow])
        @given(
            seed=st.integers(min_value=0, max_value=10_000),
            util=st.sampled_from([0.2, 0.5, 0.8, 1.2, 1.8]),
            n=st.integers(min_value=1, max_value=5),
            gn_total=st.integers(min_value=2, max_value=10),
            tight=st.booleans(),
        )
        def check(seed, util, n, gn_total, tight):
            rng = np.random.default_rng(seed)
            ts = generate_taskset(
                rng, util,
                GeneratorConfig(n_tasks=n, n_subtasks=3, variability=0.3),
            )
            d = grid_search_dfs(ts, gn_total, tightened=tight)
            f = grid_search_frontier(ts, gn_total, tightened=tight)
            _assert_same_result(d, f, f"{seed}/{util}/{n}/{gn_total}/{tight}")

        check()


class TestControllerEngines:
    def _replay(self, events, transition, engine):
        trace = EventTrace()
        c = DynamicController(10, transition=transition, trace=trace,
                              engine=engine)
        stream = []
        for ev in events:
            if ev.kind == "admit":
                dec = c.admit(ev.task, t=ev.time)
                stream.append((
                    ev.name, dec.admitted, dec.reason,
                    None if dec.alloc is None else tuple(sorted(dec.alloc.items())),
                    None if dec.bounds is None else tuple(sorted(dec.bounds.items())),
                ))
            else:
                c.release(ev.name, t=ev.time)
                c.job_boundary(ev.name, t=ev.time)
        return stream, trace.dumps()

    @pytest.mark.parametrize("preset", [
        p for p in GOLDEN_SCENARIOS if p.kind == "churn"
    ], ids=lambda p: p.name)
    def test_golden_churn_admissions_byte_identical(self, preset):
        """Golden-scenario admission decisions: batch == scalar, bytes."""
        events = preset.build_churn()
        for transition in ("boundary", "instant"):
            s_stream, s_trace = self._replay(events, transition, "scalar")
            b_stream, b_trace = self._replay(events, transition, "batch")
            assert s_stream == b_stream, (preset.name, transition)
            assert s_trace == b_trace, (preset.name, transition)

    def test_fleet_scale_churn_admissions_identical(self):
        """Small services arriving on 28 slices until about twenty are
        resident, where the batched sweep is the controller's own choice:
        batch == scalar decisions and bounds."""
        events = generate_churn_trace(
            seed=1, horizon=2500.0,
            config=ChurnConfig(mean_interarrival=110.0,
                               lifetime_range=(3500.0, 7000.0),
                               util_range=(0.02, 0.05),
                               task_config=GeneratorConfig(n_subtasks=3)),
        )
        streams, peak = [], 0
        for engine in ("scalar", "batch"):
            c = DynamicController(28, transition="instant", engine=engine)
            stream = []
            for ev in events:
                if ev.kind == "release":
                    c.release(ev.name)
                    continue
                dec = c.admit(ev.task, t=ev.time)
                stream.append((ev.name, dec.admitted, None if dec.bounds
                               is None else tuple(sorted(dec.bounds.items()))))
                peak = max(peak, len(c.allocation))
            streams.append(stream)
        assert streams[0] == streams[1]
        assert peak >= 18

    def test_engine_validation(self):
        with pytest.raises(ValueError):
            DynamicController(4, engine="nope")

    def test_backend_name_validation(self):
        with pytest.raises(ValueError):
            BatchAnalyzer(_taskset(0, 0.5), backend="nupmy")

    def test_pinned_batch_sweep_matches_scalar(self, monkeypatch):
        """Force the vectorized pinned sweep below its adaptive crossover.

        Every controller in this suite runs on small systems (gn_total
        <= 10), which adaptively dispatch to the memoized scalar loop —
        so the batched `_pinned_batch` sweep itself must be pinned-tested
        explicitly, including with residents mid-transition."""
        monkeypatch.setattr(DynamicController, "_BATCH_MIN_WORK", 1)
        events = generate_churn_trace(seed=2, horizon=4000.0,
                                      config=ChurnConfig())
        for transition in ("boundary", "instant"):
            s_stream, s_trace = self._replay(events, transition, "scalar")
            b_stream, b_trace = self._replay(events, transition, "batch")
            assert s_stream == b_stream, transition
            assert s_trace == b_trace, transition
        # staging entries (update_rate) reach the 3-vector envelope
        tasks = [ev.task for ev in events if ev.kind == "admit"]
        cs = DynamicController(10, engine="scalar")
        cb = DynamicController(10, engine="batch")
        resident = None
        for task in tasks[:4]:
            ds, db = cs.admit(task), cb.admit(task)
            assert (ds.admitted, ds.bounds) == (db.admitted, db.bounds)
            if ds.admitted and resident is None:
                resident = task
        assert resident is not None
        us = cs.update_rate(resident.name, resident.period * 1.4,
                            resident.deadline * 1.2)
        ub = cb.update_rate(resident.name, resident.period * 1.4,
                            resident.deadline * 1.2)
        assert (us.admitted, us.bounds) == (ub.admitted, ub.bounds)
        for task in tasks[4:8]:
            ds, db = cs.admit(task), cb.admit(task)  # mid-transition sweep
            assert (ds.admitted, ds.bounds, ds.reason) == \
                   (db.admitted, db.bounds, db.reason)

    def test_rejected_admit_transactional_under_batch(self):
        events = generate_churn_trace(seed=3, horizon=2500.0,
                                      config=ChurnConfig())
        c = DynamicController(4, engine="batch")
        admitted = 0
        for ev in events:
            if ev.kind != "admit":
                continue
            before = c.fingerprint()
            dec = c.admit(ev.task)
            if dec.admitted:
                admitted += 1
            else:
                assert c.fingerprint() == before
        assert admitted > 0


class TestBackends:
    def test_numpy_always_available(self):
        assert available_backends() == ("numpy", "jax")

    def test_unknown_backend_rejected(self):
        from repro.core.backend import set_backend
        with pytest.raises(ValueError):
            set_backend("cuda")

    def test_jax_backend_equivalence_subprocess(self):
        """JAX backend agrees with the scalar path to 1e-9.

        Runs in a subprocess because selecting the backend enables
        process-global float64 (jax_enable_x64)."""
        code = """
import numpy as np
from repro.core import GeneratorConfig, generate_taskset, set_backend
from repro.core.federated import grid_search_dfs
from repro.core.rta_batch import grid_search_frontier

set_backend("jax")
for seed in range(3):
    r = np.random.default_rng(seed)
    ts = generate_taskset(r, 0.6, GeneratorConfig(n_tasks=3, n_subtasks=3))
    d = grid_search_dfs(ts, 6, tightened=True)
    f = grid_search_frontier(ts, 6, tightened=True, backend="jax")
    assert d.schedulable == f.schedulable and d.alloc == f.alloc
    if d.schedulable:
        for a, b in zip(d.analysis.responses, f.analysis.responses):
            assert abs(a - b) <= 1e-9, (a, b)
print("OK")
"""
        env = dict(os.environ)
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
            env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-c", code], env=env,
            capture_output=True, text=True, timeout=560,
        )
        assert proc.returncode == 0, proc.stderr
        assert "OK" in proc.stdout
