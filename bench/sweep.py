"""One-time sweep of a cell's load on the chip, to fix its periods.

  python3 bench/sweep.py --workload <cell> --seed <n> [--seconds 4]

After the cell's set-up, every service's period and deadline are scaled
together so that the offered utilisation, the sum over services of the
median warm job time over period, steps through ``--loads``.  Each step
runs the executor for ``--seconds``.  The knee is the heaviest load at which
no job raised, at most one job per service was left queued, and the 95th
percentile of R met the deadline: the highest rate sustained within the
limit.  (Not "no missed deadline": the host stands still for a tenth of a
second now and then, and a job it catches misses at any load.)  The admission edge is
the shortest scale at which the controller admits every service, found by
bisection.  The cell's periods are then 5/4 of the longer of the two: the
cell runs at 4/5 of the highest rate that both admission and the executor
sustain.  Prints one JSON line; the periods are copied into the cell file
by hand, so that they stay fixed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
sys.path.insert(0, str(BENCH.parent / "src"))

MARGIN = 1.25


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--loads", default="0.6,0.7,0.75,0.8,0.85,0.9,0.95,1.0")
    args = ap.parse_args(argv)

    import jax
    from repro.core import set_backend

    import harness
    import jobs

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    set_backend("numpy")
    cell = harness.Cell(args.workload, args.seed, pool_jobs=256)
    cell.build()
    cell.time_warm_jobs(5)
    util_at_1 = sum(s.median_job_s / s.period_s for s in cell.services)

    steps, knee = [], None
    for load in sorted(float(x) for x in args.loads.split(",")):
        scale = util_at_1 / load
        cell.admit(scale)
        trace = cell.window(args.seconds, scale=scale)
        done = jobs.from_events(trace.events)
        st = cell.stats
        row = {"load": load, "scale": scale,
               "periods_ms": {s.name: s.spec["period_ms"] * scale
                              for s in cell.services},
               "admitted": all(s.bound_ms is not None for s in cell.services),
               "missed": sum(v["missed"] for v in st.values()),
               "errors": sum(s.errors for s in cell.services),
               "backlog": max((v["released"] - v["completed"]
                               for v in st.values()), default=0),
               "jobs": len(done)}
        within = bool(done)
        for s in cell.services:
            r = [j.response * 1e3 for j in done if j.service == s.name]
            if not r:
                within = False
                continue
            p95 = jobs.percentile(r, 95)
            row[s.name] = {"r_p50_ms": jobs.percentile(r, 50),
                           "r_p95_ms": p95, "r_max_ms": max(r)}
            within = within and p95 <= s.spec["deadline_ms"] * scale
        harness.log(json.dumps(row))
        steps.append(row)
        if within and row["errors"] == 0 and row["backlog"] <= 1:
            knee = row

    lo, hi = 0.05, 20.0           # bisection on the scale admission needs
    for _ in range(40):
        mid = (lo * hi) ** 0.5
        cell.admit(mid)
        if all(s.bound_ms is not None for s in cell.services):
            hi = mid
        else:
            lo = mid
    edge = hi
    # no load sustained: the lightest one tried, and the misses it showed
    base = max(edge, (knee or steps[0])["scale"])
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "warm_job_ms": {s.name: s.median_job_s * 1e3
                        for s in cell.services},
        "warm_decode_step_ms": {s.name: s.step_s * 1e3 for s in cell.services},
        "knee": knee, "admission_edge_periods_ms": {
            s.name: s.spec["period_ms"] * edge for s in cell.services},
        "periods_ms": {s.name: round(s.spec["period_ms"] * base * MARGIN, 1)
                       for s in cell.services},
        "steps": steps}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
