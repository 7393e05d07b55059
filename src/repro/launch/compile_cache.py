"""Where compiled XLA programs persist between processes.

Call ``enable_compile_cache()`` before the first compile of an entry point
(``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``). When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there and
nothing is set here. Otherwise the cache goes to ``.jax_cache/`` at the root
of the checkout. The directory is part of every entry's key, so it is a
fixed path: never a temporary name, a process id or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
