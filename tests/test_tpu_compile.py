"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is needed: where the TPU compiler (libtpu) is installed, it
compiles for a topology that is described, not attached; where it is not
(a CPU-only jax install), the tests skip. This catches what interpret
mode cannot — block shapes the chip cannot tile, kernels that overflow
VMEM — at qwen2-1.5b's head widths (12 query heads, 2 KV heads, head
dim 128, bf16 cache) and the engine's batch of 8 slots.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops

CFG = configs.get("qwen2-1.5b")
H, KV, DH = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
SLOTS = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it.
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_log = os.environ.get("TPU_LOG_DIR")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_flat(sharding, B, L):
    def f(q, k, v, valid):
        return ops.decode_attention(q, k, v, valid, impl="pallas",
                                    interpret=False)
    return jax.jit(f).lower(
        _spec(sharding, (B, H, DH), jnp.bfloat16),
        _spec(sharding, (B, L, KV, DH), jnp.bfloat16),
        _spec(sharding, (B, L, KV, DH), jnp.bfloat16),
        _spec(sharding, (B, L), jnp.bool_)).compile()


def _compile_paged(sharding, B, n, ps):
    def f(q, kp, vp, pages, valid):
        return ops.paged_decode_attention(q, kp, vp, pages, valid,
                                          impl="pallas", interpret=False)
    P = B * n + 1                       # every row's pages + the trash page
    return jax.jit(f).lower(
        _spec(sharding, (B, H, DH), jnp.bfloat16),
        _spec(sharding, (P, ps, KV, DH), jnp.bfloat16),
        _spec(sharding, (P, ps, KV, DH), jnp.bfloat16),
        _spec(sharding, (B, n), jnp.int32),
        _spec(sharding, (B, n * ps), jnp.bool_)).compile()


@pytest.mark.parametrize("L", [2048, 1088, 1032])
def test_flat_decode_kernel_compiles(one_chip, L):
    """Lane-aligned lengths and lengths off the 128 grid (the wrapper
    pads those) both compile at the engine's batch."""
    compiled = _compile_flat(one_chip, SLOTS, L)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("ps", [8, 16, 128])
def test_paged_decode_kernel_compiles(one_chip, ps):
    """Page sizes the engine and benchmarks use, over a 2k-token row."""
    assert ops.paged_page_size_ok(ps, DH)
    compiled = _compile_paged(one_chip, SLOTS, 2048 // ps, ps)
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_page_size_rule_matches_compiler(one_chip):
    """The largest page the rule accepts compiles; the next power of two,
    which the rule refuses, overflows the kernel's VMEM."""
    assert ops.paged_page_size_ok(15360, DH)
    _compile_paged(one_chip, SLOTS, 1, 15360)
    assert not ops.paged_page_size_ok(16384, DH)
    with pytest.raises(Exception, match="vmem"):
        _compile_paged(one_chip, SLOTS, 1, 16384)
