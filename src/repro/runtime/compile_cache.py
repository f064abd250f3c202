"""Where the entry points keep JAX's persistent compilation cache.

Called by the programs a user starts (``chip_smoke.py``,
``python -m repro.launch.serve``, ``python -m benchmarks.run``), never at
library import and never by the tests.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``.jax_cache/`` at the checkout root (git-ignored). A fixed path: the
#: directory is part of what a later run must find again to hit the cache.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
    this sets nothing. Otherwise the cache goes to :data:`DEFAULT_DIR`."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
