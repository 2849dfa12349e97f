"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called by the ``main()`` of each program that
serves on a device (``chip_smoke.py``, ``launch.serve_store``,
``launch.serve_forest``) — never at import time and never from tests.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and the location
is left to it.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``: the cache key includes nothing that moves, so
a later run in the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the repository checkout that holds ``src/repro``
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile, and return its
    directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # kernels compile in about a second: cache them all, not only the
    # compiles above JAX's default one-second floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path

