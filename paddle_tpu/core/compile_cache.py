"""Where compiled executables persist between processes.

One rule, applied once when the package is imported — so it covers every
compile path that exists (Executor, ParallelExecutor, the serving engines
and the bare `jax.jit`s under parallel/):

- `JAX_COMPILATION_CACHE_DIR` set: JAX already honours it; nothing is set
  here.
- otherwise the cache is `<checkout>/.jax_cache`, computed from this
  package's own location. The path is part of the cache key, so it is never
  a temporary directory, a pid or a timestamp: a directory that moves never
  hits.

A process pinned to the CPU platform (`JAX_PLATFORMS=cpu` — the test tier)
keeps no cache: its compiles are small, and the suite's time window is
better spent running tests than serializing executables.
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
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if (jax.config.jax_platforms or "").strip().lower() == "cpu":
        return
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
