"""Persistent XLA compilation cache placement for the entry points.

Called by ``chip_smoke.py`` and ``repro.launch.recon.main`` -- never when a
library module is imported.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins:
JAX reads it itself and nothing here overrides it.  Otherwise the cache
lives at ``<checkout>/.jax_cache`` (listed in ``.gitignore``): a fixed path,
because the path is part of the cache key, so a directory named after a
temp dir, a pid or the time would never hit again.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache(root: Path | str = CHECKOUT) -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
