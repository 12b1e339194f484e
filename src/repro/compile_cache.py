"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train`` and the analyzer scripts) calls
:func:`use_compile_cache` before its first compile, so processes of one
checkout share compiled programs.  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing here overrides it; otherwise the
cache lives at the fixed path ``<checkout>/.jax_cache`` (git-ignored).  A
fixed path matters: a cache in a fresh temporary directory never hits.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
