"""JAX's persistent compilation cache, in one place.

Every entry point that compiles for the chip (``chip_smoke.py``,
``python -m repro.launch.serve_images``, ``examples/train_vgg.py``)
calls :func:`enable_compile_cache` before its first compile:

  * with ``JAX_COMPILATION_CACHE_DIR`` set, JAX already keeps its cache
    there, and this sets nothing;
  * otherwise the cache goes to ``.jax_cache/`` at the root of the
    checkout (gitignored).  The path is fixed — never a temp name, a
    pid or a time — because it is part of the cache key: a directory
    that moves between runs never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the checkout's own cache directory (``src/repro/core`` -> root)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its one directory; returns it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
