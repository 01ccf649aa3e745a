"""Where JAX keeps its persistent compilation cache.

A cold partition run compiles one program per level shape bucket; a
second process with the same shapes can load them instead.  The cache
path is part of what makes an entry findable again, so it is fixed:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing is set
  here, so the cache stays where the environment points it.
* otherwise — ``<repo>/.jax_cache`` (listed in ``.gitignore``).  Never a
  path built from a temporary name, a pid or the time.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the partition
examples) call :func:`use_compile_cache` once, before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
