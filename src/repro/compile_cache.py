"""Where the entry points keep JAX's persistent compilation cache.

Entry points (``chip_smoke.py`` and benchmark commands) call
``use_compile_cache`` once, before their first compilation; nothing here
runs on import, so library users and the tests keep JAX's defaults.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(root) -> str:
    """Point the persistent compilation cache at a fixed directory and
    return it.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and nothing is changed; otherwise the cache goes to
    ``<root>/.jax_cache`` — a fixed path, because the path is part of what
    a later run must find again."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
