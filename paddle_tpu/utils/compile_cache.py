"""Where the persistent XLA compilation cache lives — the ONE place that says.

``chip_smoke.py`` and the benchmark (``benchmark/system.py``) call
:func:`enable_compile_cache`; nothing else in the tree names a cache directory. The directory is part of the cache
key, so it must not move between runs: it is either wherever the operator
put it (``JAX_COMPILATION_CACHE_DIR``, which jax reads by itself) or one
fixed, git-ignored directory inside the checkout — never a temp dir, a pid
or a timestamp.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The cache directory this process will use (no side effects)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. With
    ``JAX_COMPILATION_CACHE_DIR`` set, jax already honours it and no config
    is touched; otherwise point jax at ``<checkout>/.jax_cache``."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
