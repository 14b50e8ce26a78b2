"""JAX's persistent compilation cache, kept at one fixed place.

A cold process on the chip spends most of its start-up compiling; the
cache lets the next process that compiles the same programs skip that.
The directory takes part in finding an entry again, so it must not move
between runs: it is never derived from a temp name, a process id or the
time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# ``.jax_cache/`` at the root of the checkout (listed in .gitignore):
# src/repro/launch/compile_cache.py -> parents[3].
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache(path: Path = CACHE_DIR) -> str:
    """Turn the persistent compilation cache on; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it at
    import and nothing is set here.  Otherwise the cache goes to ``path``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
