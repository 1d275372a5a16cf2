"""Figs. 8–11: acceptance ratio vs taskset utilization.

One function per paper figure:
  fig8  — CPU:mem:GPU length-range ratios (2:1, 1:2, 1:8), 1- and 2-copy
  fig9  — number of subtasks M in {3, 5, 7}
  fig10 — number of tasks N in {3, 5, 7}
  fig11 — number of SMs in {5, 8, 10}

Methods: the paper's three (RTGPU Thm 5.6, self-suspension, STGM) plus our
tightened beyond-paper variant RTGPU+ (R̂3), reported separately.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import numpy as np

from repro.core import (
    GeneratorConfig,
    analyze_rtgpu,
    analyze_rtgpu_plus,
    analyze_self_suspension,
    analyze_stgm,
    generate_taskset,
    schedule,
)

METHODS = {
    "rtgpu": analyze_rtgpu,
    "rtgpu+": analyze_rtgpu_plus,
    "selfsusp": analyze_self_suspension,
    "stgm": analyze_stgm,
}

DEFAULT_UTILS = (0.3, 0.6, 0.9, 1.2, 1.6)


def acceptance(
    config: GeneratorConfig,
    gn_total: int,
    utils: Sequence[float] = DEFAULT_UTILS,
    n_sets: int = 10,
    seed: int = 0,
    max_candidates: int = 300,
    methods: Sequence[str] = tuple(METHODS),
    engine: str = "frontier",
) -> dict:
    """acceptance[method][u] = accepted fraction.

    The RTGPU methods run on the batched frontier engine by default
    (result-identical to the scalar DFS; ``tests/test_rta_batch.py`` holds
    it) — pass ``engine="dfs"`` for the scalar reference path."""
    out: dict = {m: {} for m in methods}
    for u in utils:
        acc = {m: 0 for m in methods}
        for s in range(n_sets):
            rng = np.random.default_rng(seed * 10_000 + s)
            ts = generate_taskset(rng, u, config)
            for m in methods:
                mode = "grid" if m.startswith("rtgpu") else "greedy+grid"
                r = schedule(ts, gn_total, analyzer=METHODS[m], mode=mode,
                             max_candidates=max_candidates, engine=engine)
                acc[m] += int(r.schedulable)
        for m in methods:
            out[m][u] = acc[m] / n_sets
    return out


def _emit(name: str, table: dict, rows: list):
    for method, by_u in table.items():
        for u, a in by_u.items():
            rows.append((f"{name},{method},u={u}", a))


def fig8(n_sets: int = 10, rows: list | None = None) -> list:
    rows = rows if rows is not None else []
    ratios = {"2to1": (2, 0.5, 1), "1to2": (1, 0.5, 2), "1to8": (1, 2, 8)}
    for label, ratio in ratios.items():
        for copies in (2, 1):
            cfg = GeneratorConfig(copies=copies).scaled(ratio)
            t = acceptance(cfg, gn_total=10, n_sets=n_sets)
            _emit(f"fig8_{label}_{copies}copy", t, rows)
    return rows


def fig9(n_sets: int = 10, rows: list | None = None) -> list:
    rows = rows if rows is not None else []
    for m_sub in (3, 5, 7):
        cfg = GeneratorConfig(n_subtasks=m_sub)
        t = acceptance(cfg, gn_total=10, n_sets=n_sets)
        _emit(f"fig9_M{m_sub}", t, rows)
    return rows


def fig10(n_sets: int = 10, rows: list | None = None) -> list:
    rows = rows if rows is not None else []
    for n_tasks in (3, 5, 7):
        cfg = GeneratorConfig(n_tasks=n_tasks)
        t = acceptance(cfg, gn_total=10, n_sets=n_sets)
        _emit(f"fig10_N{n_tasks}", t, rows)
    return rows


def fig11(n_sets: int = 10, rows: list | None = None) -> list:
    rows = rows if rows is not None else []
    for sms in (5, 8, 10):
        cfg = GeneratorConfig()
        t = acceptance(cfg, gn_total=sms, n_sets=n_sets)
        _emit(f"fig11_SM{sms}", t, rows)
    return rows
