"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so a path that moves never hits: the
cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when the environment names one
(JAX reads that variable itself, and this module then sets no directory),
and otherwise to one fixed directory inside the checkout, ignored by git.
Entry points call :func:`enable` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = Path(__file__).resolve().parents[2] / "artifacts" / "jax_cache"


def enable() -> Path:
    """Turn the persistent cache on for every compile, whatever its size or
    compile time, and return its directory."""
    env = os.environ.get(ENV)
    if not env:
        CHECKOUT_DIR.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_DIR))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return Path(env) if env else CHECKOUT_DIR
