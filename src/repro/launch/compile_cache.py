"""JAX persistent compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is used as
it is.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed path,
because the path is part of what a later run must find again.  Setting
``JAX_ENABLE_COMPILATION_CACHE=false`` (the test suite does) leaves the
cache off.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on for this process; returns its directory,
    or None when the cache is switched off."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # every program, however quick to compile: a chip run pays a cold
    # compile for each one it cannot find
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
