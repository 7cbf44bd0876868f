"""Where JAX keeps its persistent compilation cache.

A cold run of the device sweep compiles one step per padded-interval
bucket and shape, and the cache lets a second process skip that. Its
location is part of the cache's key, so it is fixed: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that variable itself and
nothing here overrides it; otherwise the cache lives at
``<checkout>/.jax_cache`` (listed in ``.gitignore``). Entry points call
:func:`enable_compile_cache` once, before their first compile; importing
this module changes nothing.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir(checkout) -> tuple[str, bool]:
    """The cache directory, and whether it came from the environment."""
    env = os.environ.get(ENV)
    if env:
        return env, True
    return str(Path(checkout).resolve() / ".jax_cache"), False


def enable_compile_cache(checkout) -> str:
    """Turn the persistent cache on for this process; returns its path."""
    import jax

    path, from_env = compile_cache_dir(checkout)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
