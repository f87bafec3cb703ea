"""Where JAX's persistent compilation cache lives — decided in one place.

Every entry point that compiles (``cli.main``, ``bench.py``,
``__graft_entry__.py``, ``chip_smoke.py``'s children, the ``experiments/``
scripts) calls :func:`enable_compile_cache` before its first jit. The cache
directory is part of each entry's key, so it must not move between runs: it
is either what ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads that variable
itself; nothing is set in code) or the fixed ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX at the persistent cache; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
