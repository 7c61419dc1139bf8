"""Where JAX's persistent compilation cache lives.

A cache entry is found again only under the same directory path, so the
path is fixed: `JAX_COMPILATION_CACHE_DIR` when the environment sets it
(JAX reads that variable itself, and nothing here overrides it), otherwise
`.jax_cache` at the root of the checkout. Entry points call
`setup_compile_cache()` before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout default (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory compiled programs are cached in."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def setup_compile_cache() -> str:
    """Turn the persistent cache on at `compile_cache_dir()`; returns it."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
