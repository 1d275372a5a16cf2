"""Run one cell of the benchmark once, on the chip this process finds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``; what it serves, its
configuration ``bench/configs/<config>.json``.  With ``--trace 0`` the
last line of standard output carries the end-to-end metrics, with
``--trace 1`` the per-layer ones, each read by ``bench/metrics/<name>.py``
from the run's jobs or from the profiler's trace of the first ``TRACE_S``
seconds of the window.
The numbers that decide ``correct`` are printed beside their limits as the
last lines of standard error, and last in the result line.

Exits non-zero, with no result line, when the first device is not a TPU or
there are fewer devices than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()        # set-up is timed from here

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402
import tempfile   # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
# The compile cache lives at a fixed path inside the checkout, so that every
# run of a cell there after the first finds its programs.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
sys.path.insert(0, str(CHECKOUT / "src"))

TRACE_S = 6.0     # seconds of the window the profiler records


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_or_exit(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s), found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)
    return devs


def main(argv=None) -> int:
    args = parse(argv)
    import registry

    cell_file = registry.workload(args.workload)
    devs = chips_or_exit(cell_file["chips"])
    import jax
    from repro.core import set_backend
    from repro.launch import use_compile_cache

    import harness
    import jobs as jobs_mod
    import tracefile

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    harness.log(f"compile cache: {use_compile_cache()}")
    set_backend("numpy")
    bench = registry.benchmark()
    peak = registry.peaks(devs[0].device_kind)

    pool = max(int(args.seconds * 1e3 / min(
        s["period_ms"] for s in cell_file["services"])) + 8, 16)
    cell = harness.Cell(args.workload, args.seed, pool_jobs=pool)
    cell.build()
    cell.time_warm_jobs()
    cell.admit()
    for s in cell.services:
        harness.log(f"service {s.name}: period {s.spec['period_ms']} ms, "
                    f"warm job {s.warm_job_s * 1e3!r} ms, decode step "
                    f"{s.step_s * 1e3!r} ms, certified R-hat {s.bound_ms!r} ms")

    recorder = None
    if args.trace:
        recorder = tracefile.Recorder(tempfile.mkdtemp(prefix="bench_trace_"))
        recorder.start()
    setup_seconds = time.perf_counter() - T0

    # A traced run records the first TRACE_S seconds, then stops the
    # profiler between two windows (stopping it blocks the host for
    # seconds) and runs the rest of its time untraced.  The jobs metrics
    # read are those of the last window: with the profiler off, as users
    # run.
    spans = [args.seconds]
    if args.trace and args.seconds > TRACE_S:
        spans = [TRACE_S, args.seconds - TRACE_S]
    attempted = failed = 0
    for k, seconds in enumerate(spans):
        trace = cell.window(seconds, fresh=k == 0)
        if k == 0 and recorder is not None:
            recorder.stop()
        a, f = cell.tally(seconds)
        attempted, failed = attempted + a, failed + f
    done = jobs_mod.from_events(trace.events)
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devs[:cell_file["chips"]])
    harness.log(f"responses: {len(done)} jobs in the last window")

    cell.free_program()
    found = cell.check(cell_file["check_jobs"])
    for name, r in found.items():
        harness.log(f"check {name}: {r['jobs']} jobs, {r['tokens']} "
                    f"served tokens")
    correct, compared = cell.verdict(found)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell_file["chips"], "memory_peak_bytes": int(peak_bytes)}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    record = {"jobs": done, "setup_seconds": setup_seconds, "trace": None,
              "peak": peak, "services": {s.name: s for s in cell.services}}
    if args.trace:
        record["trace"] = red = recorder.reduce()
        shutil.rmtree(recorder.directory, ignore_errors=True)
        device["busy_s"] = tracefile.device_busy_s(red)
        device["window_s"] = (red.window[1] - red.window[0]) / 1e9
        result["breakdown"] = {"device_ops": tracefile.top_ops(red),
                               "idle_gaps": tracefile.top_gaps(red)}
    metrics = {}
    for m in bench["per_layer" if args.trace else "end_to_end"]:
        if args.workload not in m.get("workloads", [args.workload]):
            continue
        value = registry.metric(m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device, compared=compared)
    for k, v in compared.items():
        print(f"compared {k} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
