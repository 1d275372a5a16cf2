"""repro.obs — observability for the RTGPU scheduling stack.

Three pieces, all zero-dependency and **off by default** (the golden
corpus and every benchmark replay byte-identically unless explicitly
enabled):

  :mod:`repro.obs.metrics`   counters / gauges / fixed-bucket histograms
                             with deterministic snapshots, Prometheus
                             text exposition and JSON dump.  Enable with
                             ``metrics.enable()`` or ``REPRO_OBS=1``.
  :mod:`repro.obs.monitor`   :class:`BoundMonitor` — observed R vs
                             certified R̂ headroom/drift per task, with
                             structured alerts and a certified
                             re-admission callback seam.
  :mod:`repro.obs.report`    ``python -m repro.obs.report <trace.json>``
                             — per-task R/R̂ table, miss budget,
                             preemption and migration summary from a
                             native-JSON trace (+ optional metrics
                             snapshot).

Control-plane spans live in :class:`repro.sched.EventTrace` (built with
``spans=True``): ``certify`` / ``pinned_sweep`` / ``grid_search`` /
``placement`` / ``migrate`` wall-clock slices anchored on the model
timeline, exported as Chrome ``X`` events next to the data-plane rows.

Metric name → emitting layer
----------------------------

``sched/controller.py`` (:class:`~repro.sched.DynamicController`):

  sched_admit_latency_ms       histogram  wall-clock of one admit() call
  sched_admit_total            counter    labels result=admitted|rejected,
                                          path=pinned|realloc|none
  sched_admit_candidates       histogram  candidate vectors analyzed per
                                          admission
  sched_pinned_sweeps_total    counter    label result=hit|miss — warm
                                          pinned path success rate
  sched_update_latency_ms      histogram  wall-clock of update_rate()
  sched_update_total           counter    label result — rate-change
                                          certification outcomes
  sched_reclaim_total          counter    departures whose slices returned
                                          to the pool

``sched/certify.py`` (:class:`~repro.sched.certify.CertificationEngine`):

  certify_analyses_total       counter    label engine — per-task fixed-
                                          point analyses actually run
  certify_memo_hits_total      counter    interference-context memo hits
  certify_memo_misses_total    counter    memo misses (→ fresh analysis)

``sched/federation.py`` (:class:`~repro.sched.CapacityBroker`):

  fleet_placement_ms           histogram  placement-order scoring time
  fleet_admit_total            counter    label result — fleet admissions
  fleet_hosts_tried            histogram  hosts offered per admission
  fleet_migrations_total       counter    departure-imbalance moves started
  fleet_residents              gauge      resident services in the fleet
  fleet_admissions_per_sec     gauge      admission throughput over the
                                          last 64 accepted admits
  placement_hosts_scanned      histogram  hosts in each placement order
                                          (post digest/drain masking)
  fleet_hosts_added_total      counter    elastic add_host joins
  fleet_hosts_retired_total    counter    drained hosts fully retired

``sched/fleet.py`` (:class:`~repro.sched.BrokerTree`):

  broker_shard_descents_total  counter    label phase=pinned|realloc —
                                          shard admissions actually
                                          descended (pruned shards never
                                          count)

``core/rta_batch.py`` (vectorized analyzer):

  rta_batch_calls_total        counter    fixed_point_batch invocations
  rta_batch_iters_total        counter    lockstep iterations summed
  rta_batch_stragglers_total   counter    entries handed to the scalar
                                          convergence tail
  rta_frontier_width           histogram  candidate prefixes per batched
                                          analyze_prefixes call

``runtime/engine.py`` (:class:`~repro.runtime.DiscreteEventEngine`):

  engine_jobs_completed_total  counter    jobs run to completion
  engine_deadline_misses_total counter    completions past the absolute
                                          deadline
  engine_response              histogram  label task — observed response
                                          times (model clock)
  engine_cpu_preemptions_total counter    CPU core hand-offs mid-segment
  engine_gpu_preemptions_total counter    preemptive-GPU kernel evictions
  engine_gpu_ctx_charged_total counter    context-switch time charged to
                                          evicted kernels (model clock)
  engine_steps_total           counter    event steps executed (either
                                          loop variant; the events/sec
                                          numerator in BENCH_engine.json)
  engine_step_width            histogram  model-time width per step — a
                                          mass at 0 exposes same-timestamp
                                          cascades (the livelock guard's
                                          territory), a heavy tail means
                                          idle horizons

``obs/monitor.py`` (:class:`BoundMonitor`):

  monitor_headroom             gauge      label task — 1 − R/R̂ of the
                                          latest job
  monitor_drift                gauge      label task — EWMA of R/R̂
  monitor_alerts_total         counter    label kind — alerts raised
  monitor_callback_errors_total counter   subscriber/alert callbacks that
                                          raised (logged + skipped, never
                                          propagated)

``sched/journal.py`` (:class:`~repro.sched.Journal`):

  journal_fsync_seconds        histogram  durable-commit latency per
                                          appended record
  journal_records_total        counter    label op — records written
  journal_checkpoint_ms        histogram  compaction (snapshot + truncate)
                                          wall-clock
  journal_checkpoints_total    counter    compactions performed

``sched/recovery.py`` (:func:`~repro.sched.recover`):

  recovery_ms                  histogram  replay + re-certification
                                          wall-clock per recovery
  recovery_replayed_records_total counter journal records folded back
  recovery_quarantined_total   counter    residents whose journaled R̂
                                          failed re-certification
  recovery_migrations_resolved_total counter label action=forward|back —
                                          dangling two-phase migrations
                                          resolved

``sched/daemon.py`` (:class:`~repro.sched.daemon.SchedulerDaemon`):

  daemon_requests_total        counter    label cmd — protocol requests
  daemon_request_errors_total  counter    requests answered with an error

``obs/compiles.py`` (JAX's ``jax.monitoring`` compile events; the
listener is installed by the first ``metrics.enable()``):

  jax_compile_seconds_total    counter    label stage=trace|lower|backend|
                                          cache_load — seconds compiling
                                          (cache_load is a part of backend)
  jax_compiles_total           counter    label fun — executables built or
                                          loaded from the persistent cache

Data-path spans are profiler annotations (``jax.profiler``), not
metrics: ``engine.*`` in ``serving/engine.py``, ``executor.*`` and
``host.gc`` in ``runtime/executor.py``.
"""
from .metrics import (  # noqa: F401
    MetricsRegistry,
    NullRegistry,
    disable,
    enable,
    enabled,
    registry,
)
from .monitor import Alert, BoundMonitor, make_readmit_callback  # noqa: F401

__all__ = [
    "MetricsRegistry",
    "NullRegistry",
    "registry",
    "enabled",
    "enable",
    "disable",
    "Alert",
    "BoundMonitor",
    "make_readmit_callback",
]
