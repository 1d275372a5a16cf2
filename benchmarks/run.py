"""The paper's schedulability figures (Figs. 8–13), on the CPU.

Prints ``name,value,derived`` CSV lines: acceptance ratios against
taskset utilization (Figs. 8–11) and the analysis against the executed
system (Figs. 12–13).  ``--full`` raises the taskset counts to the paper's
100 per level (hours on a CPU host); the default is a short pass.

These are analysis results, not speed claims: the repo's one performance
benchmark is ``bench/run.py``, on the chip.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig8,fig12]
"""
from __future__ import annotations

import argparse
import sys
import time

#: every stage name `--only` accepts, in execution order; a typo'd name
#: is an error up front, not a silently empty run
STAGES = ("fig8", "fig9", "fig10", "fig11", "fig12")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--sets", type=int, default=None,
                    help="tasksets per utilization level")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(STAGES))
    args = ap.parse_args(argv)
    n_sets = args.sets or (100 if args.full else 6)
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = sorted(only - set(STAGES))
        if unknown:
            ap.error(f"unknown --only stage(s): {', '.join(unknown)} "
                     f"(choose from {', '.join(STAGES)})")

    rows: list = []
    t0 = time.time()

    def stage(name, fn, *a, **kw):
        if only and name not in only:
            return
        t = time.time()
        fn(*a, **kw)
        print(f"# {name} done in {time.time()-t:.1f}s", file=sys.stderr)

    from benchmarks import fig12_system_validation, sched_acceptance

    stage("fig8", sched_acceptance.fig8, n_sets, rows)
    stage("fig9", sched_acceptance.fig9, n_sets, rows)
    stage("fig10", sched_acceptance.fig10, n_sets, rows)
    stage("fig11", sched_acceptance.fig11, n_sets, rows)
    stage("fig12", fig12_system_validation.run, max(4, n_sets // 2), rows=rows)

    print("name,value,derived")
    for name, value in rows:
        print(f"{name},{value},")
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
