"""Compile counter: JAX's compile-time events, counted in the metrics
registry.

:func:`install` registers one ``jax.monitoring`` duration listener (the
first :func:`repro.obs.metrics.enable` calls it).  While metrics are off
the listener returns at once.  While they are on it feeds

  jax_compile_seconds_total{stage}   seconds spent per compile stage:
                                     ``trace`` (Python to jaxpr), ``lower``
                                     (jaxpr to MLIR), ``backend`` (XLA
                                     compile, or the persistent-cache
                                     lookup that stands in for it) and
                                     ``cache_load`` (that lookup alone, a
                                     part of ``backend``)
  jax_compiles_total{fun}            executables built or loaded, one per
                                     backend compile, keyed by the
                                     compiled module's name
"""
from __future__ import annotations

from . import metrics

__all__ = ["STAGES", "install"]

#: jax.monitoring event -> ``stage`` label
STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}

_installed = False


def _listener(event: str, duration_secs: float, **kwargs) -> None:
    stage = STAGES.get(event)
    if stage is None or not metrics.enabled():
        return
    metrics.inc("jax_compile_seconds_total", duration_secs, stage=stage)
    if stage == "backend":
        metrics.inc("jax_compiles_total", fun=kwargs.get("fun_name", "?"))


def install() -> bool:
    """Register the listener once per process.  False where JAX is not
    installed (the control plane runs without it)."""
    global _installed
    if not _installed:
        try:
            import jax.monitoring
        except ImportError:
            return False
        jax.monitoring.register_event_duration_secs_listener(_listener)
        _installed = True
    return True
