"""JAX persistent compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the ``examples/``
mains) call ``enable_compile_cache()`` once at start-up; importing this
module changes nothing.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this function sets no directory.  Otherwise the cache
goes to ``<checkout>/.jax_cache/`` (git-ignored).  The directory is part of
the cache key, so it is a fixed path, never a temp name, a pid or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on the persistent compilation cache."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
