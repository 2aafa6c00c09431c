"""JAX's persistent compilation cache, placed where a run can find it again.

Full-size networks take tens of seconds to compile, so the chip smoke test
and the benchmark entry points keep compiled programs in JAX's persistent
cache.  The cache key includes the directory, so the directory must not
move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and this
    module sets no other directory;
  * otherwise: one fixed directory inside the checkout, ``.jax_cache``
    at the repository root (git-ignored).

Call :func:`enable_compile_cache` from an entry point's ``main``, never at
import: importing the library must not change process-wide JAX config.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout default: ``<repo>/.jax_cache`` (this file lives at
#: ``<repo>/src/repro/launch/compile_cache.py``)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ: Optional[Mapping[str, str]] = None) -> Path:
    """The directory the persistent cache uses: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ if environ is None else environ
    configured = env.get(ENV_VAR)
    return Path(configured) if configured else DEFAULT_CACHE_DIR


def enable_compile_cache() -> Path:
    """Turn on the persistent compilation cache and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already taken it
    from the environment, so nothing is overridden here."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
