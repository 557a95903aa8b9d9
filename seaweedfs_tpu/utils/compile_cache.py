"""Where JAX's persistent compile cache lives — decided in one place.

Every device coder constructor calls configure() before its first
compile, so a volume server stops recompiling every (geometry, batch
shape) on every boot. The rule:

- JAX_COMPILATION_CACHE_DIR set in the environment: JAX reads it itself;
  nothing is set in code, and the cache is there and nowhere else.
- otherwise: one fixed directory inside the checkout (git-ignored). The
  path is part of how a cache is found again, so it is never made from
  tempfile, a pid or the clock.
- a CPU backend places no cache: this directory travels with copies of
  the tree, and a CPU executable from another host is not safe to load.

Wherever the cache is, programs of any compile time go into it: the
Pallas kernel compiles in 0.2-0.9 s per shape (v5e, PR 21), under JAX's
default one-second floor, and a volume server meets dozens of shapes.
"""

from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str | None:
    """Place the cache (idempotent); returns the directory in use, or
    None where none is placed."""
    import jax
    if jax.default_backend() == "cpu":
        return None
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ \
            and jax.config.jax_persistent_cache_min_compile_time_secs != 0:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.config.jax_compilation_cache_dir != CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
