"""JAX's persistent compilation cache, turned on by entry points.

Programs (``chip_smoke.py``, the examples, ``benchmarks.run``) call
``enable()`` once at start-up; importing the library never does. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this module sets no
other directory. Otherwise the cache lives at a fixed path inside the
checkout, ``.jax_cache/`` (listed in ``.gitignore``): the path is part of
what a later run must find again, so it is never derived from a temporary
name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (src/repro/compile_cache.py -> repo)
REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    (JAX keeps a cache whenever it has a directory.)"""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
