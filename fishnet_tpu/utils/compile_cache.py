"""Where compiled XLA programs are kept between runs.

One function, called before the first jit by every entry point that
compiles (the serving services, the evaluator host, the trainers). A
cold start compiles every (bucket x row tier) eval program; with the
persistent cache a restart on the same checkout reloads them instead.

Placement rule: ``JAX_COMPILATION_CACHE_DIR`` belongs to whoever runs
the program. When it is set JAX reads it itself and this module names no
directory at all; otherwise the cache lives in ONE fixed directory
inside the checkout. The directory is part of JAX's cache key, so it is
never derived from a tempdir, a pid or the clock — a path that moves
never hits.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The in-checkout default (listed in .gitignore).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """The directory the persistent cache uses under the placement rule."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def configure() -> Optional[str]:
    """Turn the persistent compilation cache on. Returns the directory
    this call set in code, or None when ``JAX_COMPILATION_CACHE_DIR`` is
    set (JAX already points there; no directory is set here).

    JAX decides once, at its first compile, whether a cache is in use —
    call this before that. The default thresholds would skip programs
    that compile in under a second, which is most of the small-bucket
    eval programs, so every program is kept."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if os.environ.get(ENV_VAR):
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
