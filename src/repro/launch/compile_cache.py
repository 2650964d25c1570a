"""JAX's persistent compilation cache, placeable from outside.

``JAX_COMPILATION_CACHE_DIR``, when set, is where the cache lives: JAX
reads it itself and this module sets no other.  Otherwise the cache goes
to ``<checkout>/.jax_cache`` — a fixed path, found from this file's own
location, because the cache directory is part of what a later process must
find again.  Entry points that compile (``chip_smoke.py``,
``python -m repro.launch.serve``) turn it on; tests do not.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the fused LSTM kernels compile in well under
    # JAX's default one-second floor for cache writes
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
