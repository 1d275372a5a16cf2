"""Numerical-backend selection for the batched schedulability analyzer.

``repro.core.rta_batch`` evaluates whole frontiers of candidate allocations
with array kernels.  Two implementations exist:

  ``numpy``  (default) — vectorized NumPy; bit-compatible with the scalar
             reference path in ``repro.core.rta`` (sums are accumulated in
             the same order, so R̂ values match exactly).
  ``jax``    — ``jax.jit``/``vmap`` lockstep sweep (``lax.while_loop``)
             over stacked staircase arrays; requires float64
             (``jax_enable_x64`` is switched on when selected, which is
             process-global — select it at startup, not mid-run).

Selection, in precedence order: an explicit ``backend=`` argument to the
batched APIs, :func:`set_backend`, the ``REPRO_RTA_BACKEND`` environment
variable, else ``numpy``.  A process that runs a model on the accelerator
keeps ``numpy``: the x64 switch would turn the model's default-dtype
intermediates into float64.
"""
from __future__ import annotations

import os

__all__ = ["available_backends", "get_backend", "set_backend"]

_VALID = ("numpy", "jax")
_backend: str | None = None


def available_backends() -> tuple[str, ...]:
    return _VALID


def set_backend(name: str) -> str:
    """Select the analysis backend ("numpy" or "jax"); returns the name."""
    global _backend
    if name not in _VALID:
        raise ValueError(f"unknown RTA backend {name!r}; choose from {_VALID}")
    if name == "jax":
        import jax

        # The analysis is float64 throughout; without x64 JAX silently
        # truncates to float32 and the 1e-9 equivalence contract breaks.
        jax.config.update("jax_enable_x64", True)
    _backend = name
    return name


def get_backend() -> str:
    """The currently selected backend name (resolving env default once)."""
    global _backend
    if _backend is None:
        set_backend(os.environ.get("REPRO_RTA_BACKEND", "numpy"))
    return _backend
