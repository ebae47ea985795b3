"""JAX's persistent compilation cache, at one path per checkout.

Every entry point that compiles on the chip (``chip_smoke.py``,
``repro.launch.train``, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` before its first compile.  The cache
directory is part of each entry's key, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself, and no other directory is set here), otherwise the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
