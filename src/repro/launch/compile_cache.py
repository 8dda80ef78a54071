"""Where the program keeps JAX's persistent compilation cache.

Entry points (the serve CLI, the benchmark mains, `chip_smoke.py`) call
`enable_compile_cache()` once before their first compile; library code and
tests never do. The directory is fixed because it is part of what a later
process must find again: `JAX_COMPILATION_CACHE_DIR` when the environment
sets it (JAX reads that variable itself, so nothing is configured here),
otherwise `.jax_cache/` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
