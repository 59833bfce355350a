"""Where JAX keeps its persistent compilation cache for this repo.

Entry points (``chip_smoke.py``, ``examples/*``, ``benchmarks/run.py``)
call ``enable()`` once before their first compile, so every run of the
same code on the same machine finds the programs earlier runs compiled.
The directory is part of the cache key's reach: a path that moved (a
temp name, a pid, a timestamp) would never hit, so it is fixed.
"""

from __future__ import annotations

import os
from pathlib import Path

#: Fallback cache directory: inside the checkout, git-ignored.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads the
    variable itself, and nothing here overrides it); otherwise the cache
    is ``DEFAULT_DIR``.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
