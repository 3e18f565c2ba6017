"""JAX's persistent compilation cache for the launchers and ``chip_smoke.py``.

Called from their ``main``; importing this module sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

# Fixed, inside the checkout: a cache directory that moves never hits.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
