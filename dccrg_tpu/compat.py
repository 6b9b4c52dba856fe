"""JAX runtime set-up shared by the entry points: the persistent
compile cache."""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, so that one checkout's runs find each other's compiles: the
# cache path is part of the key, and a temp or per-run path never hits
DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def use_compile_cache(directory: str | os.PathLike | None = None) -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache stays there
    and ``directory`` is ignored. Otherwise the
    cache goes to ``directory``, by default ``<checkout>/.jax_cache``.
    Both size gates are lowered, since they would keep the CPU's small,
    fast compiles out of the cache."""
    import jax

    path = os.environ.get(CACHE_ENV) or str(
        directory if directory is not None else DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
