"""The program's own spans and compile counter, read beside the benchmark's
trace reduction (``tracefile``), and a run that records them on the chip.

The program writes profiler spans on the served path (``engine.*`` in
``ServingEngine.generate``, ``executor.*`` and ``host.gc`` in
``WallClockExecutor.run``); each ``engine.*`` step carries the RTGPU
segment kind of its work as ``segment``: ``cpu``, ``copy`` or ``device``.
Its compile counter (``repro.obs.compiles``) counts into the metrics
registry while metrics are on.  This module reads both:

- ``load`` keeps each event's ``stats`` beside the ``tracefile.Event`` row,
  so that ``tracefile.reduce`` sees exactly the rows it always saw;
- ``spans`` lists the program's spans, each with its segment and the
  ``job:`` span it falls in; ``Timeline`` cuts the window into pieces, each
  under its innermost program span;
- ``job_idle``, ``name_gaps`` and ``scoped_ops`` split in-job idle
  by segment, name every idle gap by its innermost span, and prefix every
  device op with the model scope (``decode/kv_update``) its instruction
  has in the compiled program;
- ``segment_ms``, ``compile_seconds`` and ``compiles`` compute the
  quantities ``job_cpu_segment_ms``, ``job_copy_segment_ms``,
  ``setup_compile_s`` and ``window_compiles``.

Run on a TPU (one process; the first ``--seconds`` traced, then
``--untraced`` more seconds with the profiler off):

  python3 bench/spans.py --workload <cell> --seed <n> [--seconds 6]
      [--untraced 6] [--ab <pairs>] [--out <file>]

It prints one JSON line: the four quantities above, the in-job idle split
by segment, the named gaps and scoped ops, the five per-layer metrics of
``BENCHMARK.json`` as ``run.py`` reads them, the traced and untraced jobs'
medians and, with ``--ab``, the cost of ``generate``'s spans with the
profiler off (alternating calls with and without them).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()        # set-up is timed from here

import argparse   # noqa: E402
import bisect     # noqa: E402
import contextlib  # noqa: E402
import json       # noqa: E402
import re         # noqa: E402
import shutil     # noqa: E402
import statistics  # noqa: E402
import sys        # noqa: E402
import tempfile   # noqa: E402
from pathlib import Path  # noqa: E402
from typing import NamedTuple, Optional  # noqa: E402

import tracefile  # noqa: E402
from tracefile import Event  # noqa: E402

PROGRAM_PREFIXES = ("engine.", "executor.", "host.")
SEGMENTS = ("cpu", "copy", "device")
# idle under a program span that names no segment (``engine.generate``,
# ``executor.job``, ``host.gc`` outside any step), and under none at all
OTHER, NO_SPAN = "other", "(no span)"
# model scopes (``jax.named_scope`` in ``serving/engine.py`` and
# ``models/``): the program first, then the innermost part of a layer
PROGRAM_SCOPES = ("prefill", "decode")
PART_SCOPES = ("kv_update", "attention", "mlp", "moe", "lm_head")
# the compile stages that add up to the time spent compiling
# (``cache_load`` is a part of ``backend``)
COMPILE_STAGES = ("trace", "lower", "backend")
# an HLO instruction with its op_name metadata, in ``Compiled.as_text()``
HLO_OP = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"',
                    re.M)


class Span(NamedTuple):
    name: str
    start: float
    end: float
    segment: Optional[str]
    job: Optional[int]        # index into ``Reduced.jobs``, or None


class Piece(NamedTuple):
    start: float
    end: float
    name: str                 # innermost program span, or NO_SPAN
    segment: str              # innermost segment kind, OTHER or NO_SPAN


# ------------------------------------------------------------------ reading

def load(path) -> list[tuple[Event, dict]]:
    """``tracefile.load``'s rows, each with its event's stats."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return [(Event(plane.name, line.name, ev.name, float(ev.start_ns),
                   float(ev.duration_ns)), dict(ev.stats))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def spans(rows, red: tracefile.Reduced) -> list[Span]:
    """The program's host spans, by start, each with its segment and the
    job span its start falls in."""
    job_starts = [s for _, s, _ in red.jobs]

    def job_of(t):
        i = bisect.bisect_right(job_starts, t) - 1
        return i if i >= 0 and t <= red.jobs[i][2] else None

    out = [Span(e.name, e.start, e.end, st.get("segment"), job_of(e.start))
           for e, st in rows
           if not e.plane.startswith("/device:")
           and e.name.startswith(PROGRAM_PREFIXES)]
    return sorted(out, key=lambda s: (s.start, -s.end))


class Timeline:
    """Disjoint pieces from the first span's start to the last span's end,
    each under the innermost span open there (the one opened last) and the
    innermost segment kind among the open spans."""

    def __init__(self, sp: list[Span]):
        bounds = sorted({t for s in sp for t in (s.start, s.end)})
        starts: dict[float, list[Span]] = {}
        ends: dict[float, list[Span]] = {}
        for s in sp:
            if s.end > s.start:
                starts.setdefault(s.start, []).append(s)
                ends.setdefault(s.end, []).append(s)
        stack: list[Span] = []
        self.pieces: list[Piece] = []
        for t0, t1 in zip(bounds, bounds[1:]):
            for s in ends.get(t0, ()):
                stack.remove(s)
            stack.extend(starts.get(t0, ()))
            if stack:
                seg = next((s.segment for s in reversed(stack)
                            if s.segment), OTHER)
                self.pieces.append(Piece(t0, t1, stack[-1].name, seg))
            else:
                self.pieces.append(Piece(t0, t1, NO_SPAN, NO_SPAN))
        self._starts = [p.start for p in self.pieces]

    def cut(self, lo: float, hi: float) -> list[Piece]:
        """[lo, hi] split at the pieces' edges; what no span covers is
        NO_SPAN."""
        out, t = [], lo
        i = max(bisect.bisect_right(self._starts, lo) - 1, 0)
        for p in self.pieces[i:]:
            if p.start >= hi:
                break
            if p.end <= t:
                continue
            if p.start > t:
                out.append(Piece(t, p.start, NO_SPAN, NO_SPAN))
            end = min(p.end, hi)
            out.append(Piece(max(p.start, t), end, p.name, p.segment))
            t = end
        if t < hi:
            out.append(Piece(t, hi, NO_SPAN, NO_SPAN))
        return out


def job_idle(red: tracefile.Reduced, tl: Timeline) -> list[dict]:
    """Per traced job, nanoseconds of device idle inside its span, split by
    the innermost segment kind over them: ``cpu``, ``copy``, ``device``,
    ``other`` (a program span with no segment) and ``(no span)``.  The
    parts add up to ``idle``."""
    out = []
    for _, js, je in red.jobs:
        split = dict.fromkeys(SEGMENTS + (OTHER, NO_SPAN), 0.0)
        idle = 0.0
        for s, e in tracefile.gaps(red.busy, js, je):
            idle += e - s
            for p in tl.cut(s, e):
                split[p.segment] += p.end - p.start
        out.append({"idle": idle, **split})
    return out


def name_gaps(red: tracefile.Reduced, tl: Timeline, n: int = 10) -> list:
    """``tracefile.top_gaps`` with each piece of a gap cut at program span
    edges and named by its innermost span after the job prefix:
    ``in job:<svc> engine.pull``, ``between jobs executor.idle``."""
    out = []
    for s, e in tracefile.gaps(red.busy, *red.window):
        t = s
        parts = []
        for svc, js, je in red.jobs:
            if je <= t or js >= e:
                continue
            if js > t:
                parts.append(("between jobs", t, js))
            end = min(je, e)
            parts.append((f"in job:{svc}", max(js, t), end))
            t = end
        if t < e:
            parts.append(("between jobs", t, e))
        for label, lo, hi in parts:
            for p in tl.cut(lo, hi):
                out.append([f"{label} {p.name}", (p.end - p.start) / 1e9])
    return sorted(out, key=lambda g: -g[1])[:n]


def scope_of(op_name: str) -> str:
    """``<program>/<part>`` from the model scopes in an op's ``op_name``
    path, e.g. ``decode/kv_update``; '' if it holds none."""
    parts = op_name.split("/")
    prog = next((p for p in parts if p in PROGRAM_SCOPES), None)
    part = next((p for p in reversed(parts) if p in PART_SCOPES), None)
    return "/".join(p for p in (prog, part) if p)


def hlo_scopes(hlo_text: str) -> tuple[str, dict[str, str]]:
    """A compiled module's name and, per instruction, the scope of its
    ``op_name``.  The device trace names each op by its instruction and
    carries no ``op_name``, so the scopes come from the program's HLO."""
    module = hlo_text.split(",", 1)[0].split()[-1]
    return module, {m[1]: sc for m in HLO_OP.finditer(hlo_text)
                    if (sc := scope_of(m[2]))}


def scoped_ops(red: tracefile.Reduced, scopes: dict, n: int = 10) -> list:
    """``tracefile.top_ops`` keyed by ``<scope>: <op>``, the scope looked up
    in ``scopes`` (module name -> ``hlo_scopes``) under the module that
    runs when the op starts; the device time summed over all ops is the
    same as ``top_ops``'s."""
    starts = [m.start for m in red.modules]
    total: dict[str, float] = {}
    lo, hi = red.window
    for e in red.ops:
        part = min(e.end, hi) - max(e.start, lo)
        if part <= 0:
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        module = red.modules[i].name.split("(")[0] \
            if i >= 0 and e.start <= red.modules[i].end else None
        instr = e.name.split(" = ", 1)[0].lstrip("%")
        scope = scopes.get(module, {}).get(instr)
        key = f"{scope}: {e.name}" if scope else e.name
        total[key] = total.get(key, 0.0) + part
    return [[k, v / 1e9] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def program_scopes(service) -> dict:
    """``hlo_scopes`` of a service's compiled prefill and decode, lowered
    on the arguments ``generate`` passes them (the persistent cache hands
    back the executables that ran)."""
    import jax.numpy as jnp

    eng, spec = service.engine, service.spec
    b, s = spec["batch"], spec["prompt_len"]
    caches = eng.model.init_caches(b, spec["max_context"])
    calls = ((eng._prefill, (eng.params, jnp.zeros((b, s), jnp.int32),
                             caches)),
             (eng._decode, (eng.params, jnp.zeros((b, 1), jnp.int32), caches,
                            jnp.full((b,), s, jnp.int32))))
    return dict(hlo_scopes(fn.lower(*a).compile().as_text())
                for fn, a in calls)


# ------------------------------------------------------------- quantities

def job_segments(red: tracefile.Reduced, sp: list[Span]) -> list[dict]:
    """Per traced job, milliseconds of each segment kind's spans."""
    per = [dict.fromkeys(SEGMENTS, 0.0) for _ in red.jobs]
    for s in sp:
        if s.job is not None and s.segment in SEGMENTS:
            per[s.job][s.segment] += (s.end - s.start) / 1e6
    return per


def span_ms_by_name(red: tracefile.Reduced, sp: list[Span]) -> dict:
    """Per span name, the median over traced jobs of the per-job sum of its
    spans, in ms."""
    per: dict[str, list[float]] = {}
    for s in sp:
        if s.job is not None:
            per.setdefault(s.name, [0.0] * len(red.jobs))[s.job] += \
                (s.end - s.start) / 1e6
    return {k: statistics.median(v) for k, v in sorted(per.items())}


def segment_ms(red: tracefile.Reduced, sp: list[Span],
               kind: str) -> Optional[float]:
    """Median over traced jobs of the per-job sum of ``kind`` spans, in
    ms; None where no job holds a span with a segment."""
    per = [j for j in job_segments(red, sp) if any(j.values())]
    return statistics.median(j[kind] for j in per) if per else None


def compile_seconds(snapshot: dict) -> Optional[float]:
    """Seconds compiling in a metrics snapshot: trace, lower and backend
    (cache loads are inside backend); None without the counter."""
    fam = snapshot.get("jax_compile_seconds_total")
    if fam is None:
        return None
    return sum(fam["series"].get(f"stage={s}", 0.0) for s in COMPILE_STAGES)


def compiles(snapshot: dict) -> Optional[dict]:
    """Compiles by ``fun`` in a metrics snapshot; None without the
    counter (metrics off)."""
    fam = snapshot.get("jax_compiles_total")
    if fam is None:
        return None
    return {k.split("=", 1)[1]: v for k, v in fam["series"].items()}


def compiles_between(before: Optional[dict], after: Optional[dict]) -> dict:
    before = before or {}
    return {f: n - before.get(f, 0.0) for f, n in (after or {}).items()
            if n > before.get(f, 0.0)}


# -------------------------------------------------------------- the run

def _no_span(name, **meta):
    return contextlib.nullcontext()


def span_ab(service, pairs: int) -> dict:
    """``generate`` with and without its spans, alternating call by call
    (the profiler off): in µs per job, the mean of the paired differences
    with its standard error, and their median with the order statistics
    that bound it at 95 %."""
    from repro.serving import engine as engine_mod

    real = engine_mod.span
    diffs, on_s, off_s = [], [], []
    try:
        for i in range(pairs):
            times = {}
            for arm in ((True, False) if i % 2 else (False, True)):
                engine_mod.span = real if arm else _no_span
                prompts = service.pool[(2 * i + arm) % len(service.pool)]
                t = time.perf_counter()
                service.engine.generate(
                    prompts, max_new_tokens=service.spec["new_tokens"])
                times[arm] = time.perf_counter() - t
            on_s.append(times[True])
            off_s.append(times[False])
            diffs.append((times[True] - times[False]) * 1e6)
    finally:
        engine_mod.span = real
    n = len(diffs)
    ranked = sorted(diffs)
    k = max(int(n / 2 - 0.98 * n ** 0.5), 0)      # sign-test interval
    return {"pairs": n, "on_ms_median": statistics.median(on_s) * 1e3,
            "off_ms_median": statistics.median(off_s) * 1e3,
            "us_per_job_mean": statistics.fmean(diffs),
            "us_per_job_sem": statistics.stdev(diffs) / n ** 0.5
            if n > 1 else None,
            "us_per_job_median": statistics.median(diffs),
            "us_per_job_median_ci95": [ranked[k], ranked[n - 1 - k]]}


def span_cost_us(n: int = 100_000) -> dict:
    """µs per span on this host with the profiler off, bare and with the
    metadata a step span carries."""
    from jax.profiler import TraceAnnotation

    t = time.perf_counter()
    for _ in range(n):
        with TraceAnnotation("engine.x"):
            pass
    bare = (time.perf_counter() - t) / n * 1e6
    t = time.perf_counter()
    for i in range(n):
        with TraceAnnotation("engine.x", segment="cpu", step=i):
            pass
    meta = (time.perf_counter() - t) / n * 1e6
    return {"bare": bare, "with_metadata": meta}


def _median_run_ms(jobs) -> Optional[float]:
    runs = [(j.complete - j.start) * 1e3 for j in jobs]
    return statistics.median(runs) if runs else None


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0,
                    help="seconds of the window the profiler records")
    ap.add_argument("--untraced", type=float, default=6.0,
                    help="seconds of window after the profiler stops")
    ap.add_argument("--ab", type=int, default=0,
                    help="pairs of generate calls with and without spans")
    ap.add_argument("--out", default=None, help="also write the JSON here")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    import registry
    from run import chips_or_exit

    cell_file = registry.workload(args.workload)
    devs = chips_or_exit(cell_file["chips"])
    import jax
    from repro.core import set_backend
    from repro.launch import use_compile_cache
    from repro.obs import metrics

    import harness
    import jobs as jobs_mod

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    harness.log(f"compile cache: {use_compile_cache()}")
    set_backend("numpy")
    metrics.enable(fresh=True)
    bench = registry.benchmark()
    peak = registry.peaks(devs[0].device_kind)

    seconds = args.seconds + args.untraced
    pool = max(int(seconds * 1e3 / min(
        s["period_ms"] for s in cell_file["services"])) + 8, 16)
    cell = harness.Cell(args.workload, args.seed, pool_jobs=pool)
    cell.build()
    cell.time_warm_jobs()
    cell.admit()
    ab = span_ab(cell.services[0], args.ab) if args.ab else None
    setup = compiles(metrics.registry().snapshot())
    setup_compile = compile_seconds(metrics.registry().snapshot())

    recorder = tracefile.Recorder(tempfile.mkdtemp(prefix="bench_spans_"))
    recorder.start()
    setup_seconds = time.perf_counter() - T0
    traced = cell.window(args.seconds, fresh=True)
    recorder.stop()
    untraced = cell.window(args.untraced, fresh=False) \
        if args.untraced > 0 else None
    window = compiles_between(setup, compiles(metrics.registry().snapshot()))
    for fun, n in sorted(window.items()):
        harness.log(f"compiled in the window: {fun} x{n:g}")
    scopes = {}
    for s in cell.services:
        scopes.update(program_scopes(s))

    rows = load(tracefile.find_xplane(recorder.directory))
    shutil.rmtree(recorder.directory, ignore_errors=True)
    red = tracefile.reduce([e for e, _ in rows])
    sp = spans(rows, red)
    tl = Timeline(sp)
    idle = job_idle(red, tl)
    per_job = job_segments(red, sp)
    idle_total = sum(j["idle"] for j in idle)
    explained = sum(j["cpu"] + j["copy"] for j in per_job) \
        + sum(j["device"] for j in idle) / 1e6

    traced_jobs = jobs_mod.from_events(traced.events)
    last = jobs_mod.from_events(untraced.events) if untraced else traced_jobs
    record = {"jobs": last, "setup_seconds": setup_seconds, "trace": red,
              "peak": peak, "services": {s.name: s for s in cell.services}}
    existing = {}
    for m in bench["per_layer"]:
        if args.workload in m.get("workloads", [args.workload]):
            v = registry.metric(m["name"]).read(record)
            if v is not None:
                existing[m["name"]] = v
    result = {
        "device": {"kind": devs[0].device_kind,
                   "busy_s": tracefile.device_busy_s(red),
                   "window_s": (red.window[1] - red.window[0]) / 1e9},
        "quantities": {
            "job_cpu_segment_ms": segment_ms(red, sp, "cpu"),
            "job_copy_segment_ms": segment_ms(red, sp, "copy"),
            "setup_compile_s": setup_compile,
            "window_compiles": sum(window.values()) if setup is not None
            else None},
        "per_layer_as_run_reads_them": existing,
        "jobs_traced": len(red.jobs), "spans": len(sp),
        "in_job_idle_ms": idle_total / 1e6,
        "in_job_idle_split_ms": {
            k: sum(j[k] for j in idle) / 1e6 for k in
            SEGMENTS + (OTHER, NO_SPAN)},
        "unexplained_share": sum(j[NO_SPAN] for j in idle) / idle_total
        if idle_total else None,
        "segments_over_idle": explained / (idle_total / 1e6)
        if idle_total else None,
        "job_ms_median": statistics.median(
            (e - s) / 1e6 for _, s, e in red.jobs) if red.jobs else None,
        "job_idle_ms_median": statistics.median(
            j["idle"] / 1e6 for j in idle) if idle else None,
        "job_segments_ms_median": {
            k: statistics.median(j[k] for j in per_job) if per_job else None
            for k in SEGMENTS},
        "job_idle_under_device_ms_median": statistics.median(
            j["device"] / 1e6 for j in idle) if idle else None,
        "job_span_ms_median": span_ms_by_name(red, sp),
        "run_ms_median_traced": _median_run_ms(traced_jobs),
        "run_ms_median_untraced": _median_run_ms(last) if untraced else None,
        "compiled_in_window": window,
        "span_cost_us": span_cost_us(),
        "ab": ab,
        "idle_gaps": name_gaps(red, tl),
        "idle_gaps_plain": tracefile.top_gaps(red),
        "device_ops": scoped_ops(red, scopes),
        "device_ops_plain": tracefile.top_ops(red),
    }
    line = json.dumps(result, default=str)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
