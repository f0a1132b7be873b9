"""JAX's persistent compilation cache for the entry points.

A cold process compiles every tile program at every shape it serves; the
persistent cache lets the next process load them instead. The directory
is part of what makes a hit, so it never moves: ``$JAX_COMPILATION_CACHE_DIR``
when the environment sets it, else ``.jax_cache/`` at the repository root
(listed in ``.gitignore``). Entry points call ``enable_compile_cache()``
from their ``main``; importing this module changes nothing, so tests and
library users keep JAX's own settings.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def compile_cache_dir() -> Path:
    """Where the cache lives: the environment's directory if set, else
    the fixed in-repo one."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else DEFAULT_DIR


def enable_compile_cache() -> Path:
    """Turn the persistent compilation cache on at ``compile_cache_dir()``
    and return that directory."""
    path = compile_cache_dir()
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_enable_compilation_cache", True)
    return path
