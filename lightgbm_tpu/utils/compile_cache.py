"""Placement of JAX's persistent compilation cache.

A cold grow program compiles for 40 s on one v5e chip and 108 s under a
four-device ``shard_map`` (chip_smoke.py observations, PR 22) and every
process recompiles it, so the package points JAX at a persistent cache once,
at import (``lightgbm_tpu/__init__.py``): ``lgb.train``, ``python -m
lightgbm_tpu``, ``chip_smoke.py`` and ``benchmarks/run.py`` share it.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and this
  module sets no directory in code, so whoever runs the program places the
  cache from outside.
- unset: one fixed path inside the checkout, :data:`DEFAULT_DIR`.  Never a
  path built from ``tempfile``, a pid or the time: the directory is part of
  the cache key, so one that moves between processes never hits.
- unset, and the process is pinned to the CPU backend (``JAX_PLATFORMS=cpu``,
  as the tests are): no directory.  The cache is for the accelerator's
  executables.  XLA:CPU compiles in seconds, its executables are specific
  to the host's CPU, and its loader logs an error on every hit
  (``cpu_aot_loader``: "could lead to execution errors such as SIGILL").
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — listed in ``.gitignore`` and ``.chiprunignore``
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def configure() -> None:
    """Apply the placement rule above."""
    from ..obs.tracer import get_tracer
    with get_tracer().span("lgbm/booster/init/compile_cache"):
        _configure()


def _configure() -> None:
    pinned_to_cpu = (jax.config.jax_platforms or "").split(",")[0] == "cpu"
    if not os.environ.get(ENV_VAR) and not pinned_to_cpu:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # Thresholds: cache every program.  A training process is one large
    # program (the grower) surrounded by many sub-second ones
    # (gradients, score updates, eager ops, the hist_variant election's
    # candidates).  JAX's default 1 s floor would recompile all of the small
    # ones in every process, and a floor anywhere above zero makes "was it
    # cached" depend on how long a compile happened to take, so a second
    # identical run could still add entries.  Entry size has no floor either
    # (0 is JAX's default, stated here because the two belong together).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def cache_dir() -> "str | None":
    """The directory JAX's persistent cache uses in this process, if any."""
    return jax.config.jax_compilation_cache_dir


def entry_count() -> int:
    """Number of cached executables in :func:`cache_dir` (0 if absent)."""
    try:
        names = os.listdir(cache_dir() or "")
    except FileNotFoundError:
        return 0
    return sum(1 for n in names if n.endswith("-cache"))
