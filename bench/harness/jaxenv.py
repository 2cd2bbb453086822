"""Start JAX for a benchmark process: the TPU only, and the persistent
compile cache at a fixed path inside the checkout."""
from __future__ import annotations

import os
import pathlib

# ``<checkout>/.jax_cache``: the directory the program's own entry points
# default to, so the checkout holds one cache
CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def start() -> list:
    """Configure and import JAX; returns the list that collects one entry
    per function traced for compilation from here on."""
    os.environ["JAX_PLATFORMS"] = "tpu"
    # the program's entry points take the cache this variable names
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiles.append(name)
        if name == COMPILE_EVENT else None)
    return compiles
