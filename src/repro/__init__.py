"""repro — DDSL reproduction: distributed & dynamic subgraph listing."""

from __future__ import annotations

import os

__version__ = "0.1.0"

# <checkout>/src/repro/__init__.py → <checkout>/.jax_cache
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    The one place the cache is configured. ``JAX_COMPILATION_CACHE_DIR``,
    when set, wins untouched (JAX reads it itself). Otherwise the cache
    lives at a fixed path inside the checkout, ``.jax_cache`` — never a
    temporary or per-run name, which the next run could not find. Entry
    points (``chip_smoke.py``, the examples) call this before their
    first compile.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE
