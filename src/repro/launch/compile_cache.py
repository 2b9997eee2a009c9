"""Where JAX's persistent compilation cache lives.

The cache directory is part of every entry's key, so it must not move from
run to run. ``JAX_COMPILATION_CACHE_DIR``, when set, is the owner's choice
and JAX reads it itself: nothing here overrides it. Otherwise the cache goes
to one fixed directory inside the checkout (``<repo>/.jax_cache``, ignored
by git), never to a path derived from a temporary name, a process id or the
time. Entry points call ``setup_compile_cache()`` once at startup.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """The cache directory a process started with ``environ`` uses."""
    environ = os.environ if environ is None else environ
    return environ.get(ENV) or str(REPO_CACHE)


def setup_compile_cache() -> str:
    """Point JAX's persistent cache at ``compile_cache_dir()``; returns it."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
