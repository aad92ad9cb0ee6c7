"""Persistent XLA compilation cache.

Placement programs are compiled once per (node bucket, ask bucket,
batch bucket, full/delta variant) shape. The persistent cache makes
that a one-time cost per machine instead of per process.

Where the cache lives is decided from OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory at all. Otherwise the cache is
``<checkout>/.jax_cache`` — a fixed path, because the path is part of
the cache key's lookup and a directory that moves never hits.

The reference has no analog — Go compiles ahead of time; this is the
TPU-runtime counterpart of shipping a compiled binary.
"""

from __future__ import annotations

import os

_enabled = False


def enable_compilation_cache() -> None:
    """Idempotent; call before the first jit dispatch. A cache that
    cannot be set up raises: a cold storm that silently recompiles
    every program is a minutes-long stall, not an optimization lost."""
    global _enabled
    if _enabled:
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        repo = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        path = os.path.join(repo, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    _enabled = True
