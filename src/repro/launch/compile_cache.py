"""JAX's persistent compilation cache, kept at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here changes it.  Otherwise the cache lives in ``<repo>/.jax_cache``: a fixed
path with no temporary name, pid or time in it, since a cache that moves
never hits.  Entry points call :func:`enable_compile_cache` before their
first compile.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def compile_cache_dir(environ: Mapping[str, str]) -> Optional[str]:
    """The directory this process must set, or None where the environment
    already names one for JAX."""
    if environ.get(ENV_VAR):
        return None
    return str(DEFAULT_DIR)


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent cache at the directory the rule above picks;
    returns the directory in effect."""
    import jax
    path = compile_cache_dir(os.environ)
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return jax.config.jax_compilation_cache_dir
