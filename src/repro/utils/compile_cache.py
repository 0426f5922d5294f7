"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` from ``main()``; importing this
module changes nothing.  The cache's directory is part of what makes an
entry hit, so it is a fixed path: the one ``JAX_COMPILATION_CACHE_DIR``
names when that is set (JAX reads the variable itself), else
``.jax_cache/`` at the root of this checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
