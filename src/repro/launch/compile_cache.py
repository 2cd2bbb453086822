"""Persistent XLA compilation cache for the command-line entry points.

Called from each entry point's ``main`` (never on import): serving, the
service, the kernel benchmark and ``chip_smoke.py``. A run on a fresh
machine compiles every program; the cache lets the processes of one run,
and later runs that find the directory, reuse what was compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# A fixed path: the directory is part of each entry's key, so a cache whose
# path changed between runs (a temp name, a pid) would never hit.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at ``<repo>/.jax_cache``.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
