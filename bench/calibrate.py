"""Readings that the limits of ``correct`` are set from, on the chip.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--seconds 3]

For each seed, in this one process: the cell's set-up, a short window at the
cell's own load, then the plain reference on the same sample of finished
jobs that a run checks.  Prints, per seed and service, the program's widest
gap (the lower reading) and the fp8 control's (the upper reading), one JSON
line each, with the verdict a run would give the program and the control
against the cell's present limits.  The benchmark's own runs never run the control.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window per seed; default: long enough for every "
                         "service to finish the jobs a run checks, plus one")
    args = ap.parse_args(argv)

    import jax
    from repro.core import set_backend

    import harness
    import registry

    cell_file = registry.workload(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    set_backend("numpy")
    seconds = args.seconds or max(
        (cell_file["check_jobs"] + 2) * s["period_ms"] / 1e3
        for s in cell_file["services"])
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(args.workload, seed, pool_jobs=64)
        cell.build()
        cell.time_warm_jobs(1)
        cell.admit()
        cell.window(seconds)
        cell.free_program()
        found = cell.check(cell_file["check_jobs"], control=True)
        # the control in the program's place, judged by the run's own verdict
        control = {n: dict(r, gap=r["control_gap"]) for n, r in found.items()}
        for name, r in found.items():
            print(json.dumps({"seed": seed, "service": name, **r,
                              "correct": cell.verdict(found)[0],
                              "control_correct": cell.verdict(control)[0]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
