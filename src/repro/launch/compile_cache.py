"""Where the entry points keep JAX's persistent compilation cache.

Called by ``chip_smoke.py`` and ``repro.launch.serve`` at start-up, never
when a library module is imported.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at the fixed
    ``<checkout>/.jax_cache`` (git ignores it).  The path is part of the
    cache key, so a directory that moved would never hit.  Returns the
    directory."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
