"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points call ``place_compilation_cache()`` from ``main()``, before their
first compile; importing this module sets nothing. A directory already set
in ``jax.config`` wins (JAX fills it from ``JAX_COMPILATION_CACHE_DIR``);
otherwise the cache goes to the fixed ``<checkout>/.jax_cache``, so every
run from one checkout finds the programs an earlier run compiled. No other
code sets a cache directory.
"""
from __future__ import annotations

from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def place_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    current = jax.config.jax_compilation_cache_dir
    if current:
        return current
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
