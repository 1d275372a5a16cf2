"""Launcher: compile cache, production mesh, step builders, dry-run,
train/serve drivers.

NB: do NOT import dryrun here — it sets XLA_FLAGS at import time.
"""
from .cache import use_compile_cache
from .mesh import make_host_mesh, make_production_mesh

__all__ = ["make_host_mesh", "make_production_mesh", "use_compile_cache"]
