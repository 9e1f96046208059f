"""JAX's persistent compilation cache, configured once per entry point.

A cold compile of a full-width model's decode window takes tens of
seconds; the persistent cache lets a later run load it instead. A later
run finds the entries only where an earlier one put them, so the
directory never moves between runs (no temp, pid- or time-derived path):
``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself), else ``<checkout>/.jax_cache``.

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()      # before the first compile
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its fixed directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
