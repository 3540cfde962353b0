"""Where JAX's persistent compilation cache lives.

Serving sweeps are unrolled at trace time, so a cold process pays the
whole compile on every start; the persistent cache lets a second
process (or a later run on the same machine) skip it.  The cache key
includes the directory path, so the path must be stable: it never
depends on a temporary name, a process id or the time.

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()          # before the first compile
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (listed in .gitignore): src/repro/launch/ -> root
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing is set here; otherwise the cache goes to the
    fixed :data:`DEFAULT_DIR` inside the checkout."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
