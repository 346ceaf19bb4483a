"""Where JAX's persistent compilation cache lives, for the entry points.

Entry points (``chip_smoke.py``, ``launch/solve.py``, ``launch/mesh.py``,
``serve/pipeline.py``) call ``use_compile_cache()`` once, before their
first compile. Library code and tests never do.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other directory is set here. Otherwise the cache is ``<checkout>/.jax_cache``
— a fixed path, because the path is part of what a later process must
match to find the entries again.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
)
CHECKOUT_CACHE_DIR = os.path.normpath(CHECKOUT_CACHE_DIR)


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
