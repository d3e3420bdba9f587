"""Where compiled executables persist between processes.

One rule, applied once when the package is imported — so it covers every
compile path that exists (Executor, ParallelExecutor, the serving engines
and the bare `jax.jit`s under parallel/):

- `JAX_COMPILATION_CACHE_DIR` set: JAX already honours it; no directory is
  set here.
- otherwise the cache is `<checkout>/.jax_cache`, computed from this
  package's own location. The path is part of the cache key, so it is never
  a temporary directory, a pid or a timestamp: a directory that moves never
  hits.
- wherever a directory is in effect, from either bullet, the cache admits
  EVERY executable the process compiles: JAX's
  `jax_persistent_cache_min_compile_time_secs` goes from its default of 1 s
  to 0, unless `JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS` names one from
  outside (left as given, like the directory). At the default JAX writes
  nothing that compiled in under a second, and a process on a filled cache
  compiles its weight generators, eager `jnp` ops and startup programs
  anew, dozens of executables of a serving set-up. The rule is about WHAT
  is written, not where.
- a bound on the directory's size (`JAX_COMPILATION_CACHE_MAX_SIZE`) is
  JAX's own and never set here. Under one JAX lists the directory and reads
  a timestamp file an entry at EVERY write, so with every executable
  admitted a process that builds hundreds of new programs pays for the
  bound by the entry (PERF.md section 6, PR 58, has the seconds).

A process pinned to the CPU platform (`JAX_PLATFORMS=cpu` — the test tier)
with no directory given keeps no cache, and nothing is set for it: its
compiles are small, and the suite's time window is better spent running
tests than serializing executables.
"""

from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> None:
    """Apply the rule above. Touches `jax.config` only — no backend is
    initialized and no directory is created until JAX first writes."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if (jax.config.jax_platforms or "").strip().lower() == "cpu":
            return
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
