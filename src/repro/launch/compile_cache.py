"""JAX's persistent compilation cache for the entry points.

Every entry point (``chip_smoke.py``, ``launch/train.py``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` before its first
compile, so a later process on the same checkout loads the compiled
programs instead of compiling them again.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache (listed in .gitignore). A fixed path: a directory
# named from a temp name, a pid or the clock would never be found again.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
    nothing is changed. Otherwise the cache goes to :data:`DEFAULT_DIR`.
    Call it before the process compiles anything — JAX decides once, at the
    first compile, whether the cache is in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
