"""Where compiled device programs are kept between processes.

A cold TPU compile of one kernel shape takes seconds to tens of
seconds, and every daemon process would pay it again for every shape
it serves.  JAX's persistent compilation cache removes that — if its
directory stays put: the path is part of the cache key's world, so a
directory that moves (a temporary name, a pid, the time) never hits.

The one rule, applied by :func:`place` before the first compile of any
device path (the kernel modules call it when they import jax; host-only
processes never import either):

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is
    set in code.
  * unset: ``<checkout>/.jax_cache`` (git-ignored) — except in a
    process pinned to the CPU platform (``JAX_PLATFORMS=cpu``: the
    tests and their children), which gets none: the CPU backend
    compiles in a fraction of a second, and every load of a cached
    CPU program logs a machine-feature complaint.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
            return
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # 13 OSDs in one process each jit the same few programs: keep
    # every compile, not only the slow ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def directory() -> str | None:
    """The cache directory in force (what a report should print)."""
    import jax
    return jax.config.jax_compilation_cache_dir
