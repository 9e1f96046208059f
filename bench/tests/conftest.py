"""Benchmark tests run on the CPU with the persistent compile cache off."""

import pytest


@pytest.fixture
def no_compile_cache():
    import jax
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
