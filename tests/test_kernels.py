"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode).

Brief requirement: "For each Pallas kernel, sweep shapes/dtypes and
assert_allclose against the ref.py pure-jnp oracle."
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.key(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # B, Sq, Sk, H, KV, dh, causal, window
    (2, 128, 128, 4, 2, 64, True, None),
    (1, 256, 256, 4, 4, 64, True, 64),
    (2, 128, 256, 4, 1, 64, True, None),      # Sq < Sk (right-aligned)
    (1, 128, 128, 2, 2, 32, False, None),     # encoder / bidirectional
    (1, 512, 512, 8, 2, 128, True, 128),      # GQA + window
    (3, 64, 64, 2, 1, 128, True, None),       # MQA
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dtype):
    B, Sq, Sk, H, KV, dh, causal, window = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, Sk, KV, dh), dtype)
    v = jax.random.normal(ks[2], (B, Sk, KV, dh), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
    expect = ref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


def test_flash_attention_block_shape_invariance():
    B, S, H, KV, dh = 1, 256, 4, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, dh), jnp.float32)
    outs = [np.asarray(ops.flash_attention(q, k, v, block_q=bq, block_k=bk,
                                           interpret=True))
            for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

DECODE_CASES = [
    (2, 4, 2, 64, 512),
    (1, 8, 1, 128, 1024),
    (3, 4, 4, 32, 512),
    (1, 16, 8, 128, 2048),
    (8, 4, 2, 64, 520),       # engine-sized batch, L off the lane grid
    (2, 4, 2, 64, 40),        # L shorter than one lane block
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(case, dtype):
    B, H, KV, dh, L = case
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, L, KV, dh), dtype)
    v = jax.random.normal(ks[2], (B, L, KV, dh), dtype)
    valid = jax.random.bernoulli(ks[3], 0.7, (B, L)).at[:, 0].set(True)
    out = ops.decode_attention(q, k, v, valid, block_l=256, interpret=True)
    expect = ref.decode_attention(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


def test_decode_attention_dispatch_paths_agree():
    """Both dispatcher leaves — the Pallas body (interpret) and the jit'd
    oracle — must agree on ragged masks INCLUDING an all-invalid row,
    where the shared contract is zeros (the kernel's online-softmax
    accumulator never runs for such a row)."""
    B, H, KV, dh, L = 3, 4, 2, 64, 256
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, L, KV, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, L, KV, dh), jnp.float32)
    valid = jax.random.bernoulli(ks[3], 0.5, (B, L))
    valid = valid.at[0].set(True).at[1].set(False)   # full / empty / ragged
    out_pl = ops.decode_attention(q, k, v, valid, block_l=64,
                                  impl="pallas", interpret=True)
    out_ref = ops.decode_attention(q, k, v, valid, impl="ref")
    np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(out_pl[1]),
                                  np.zeros((H, dh), np.float32))


def test_decode_dispatch_resolution():
    """Dispatch priority: explicit impl > interpret flag > backend default
    (ref everywhere but TPU)."""
    assert ops.resolve_decode_impl(impl="ref") == "ref"
    assert ops.resolve_decode_impl(impl="ref", interpret=True) == "ref"
    assert ops.resolve_decode_impl(impl="pallas") == "pallas"
    assert ops.resolve_decode_impl(impl="pallas", interpret=False) == "pallas"
    assert ops.resolve_decode_impl(interpret=True) == "pallas"
    assert ops.resolve_decode_impl(interpret=False) == "pallas"
    default = ops.resolve_decode_impl()
    assert default == ("pallas" if jax.default_backend() == "tpu"
                       else "ref")
    with pytest.raises(ValueError):
        ops.resolve_decode_impl(impl="dense")


@pytest.mark.parametrize("L,want,block", [
    (40, 512, 40), (512, 512, 512), (520, 512, 384), (1088, 512, 384),
    (2048, 512, 512), (1032, 512, 384), (256, 64, 128)])
def test_decode_block_is_lane_aligned(L, want, block):
    """The flat kernel's block covers L with the least padding, and is a
    multiple of the 128-lane width unless one block holds all of L."""
    got = ops._block_l(L, want)
    assert got == block
    assert got == L or got % ops.LANE == 0


@pytest.mark.parametrize("L,cache_len", [
    (40, 40), (512, 512), (520, 768), (1032, 1152), (1088, 1152),
    (2048, 2048), (2049, 2560)])
def test_decode_cache_len_needs_no_pad(L, cache_len):
    """A cache allocated at decode_cache_len(L) holds L, is a whole number
    of the kernel's blocks (the wrapper pads nothing), and is a fixed
    point."""
    got = ops.decode_cache_len(L)
    assert got == cache_len
    assert got % ops._block_l(got, ops.DECODE_BLOCK_L) == 0
    assert ops.decode_cache_len(got) == got


def test_paged_page_size_rule():
    """Pages whose K/V blocks (double-buffered) overflow the kernel's
    VMEM budget are refused; every smaller page tiles."""
    for ps in (1, 8, 16, 100, 1024, 15360):
        assert ops.paged_page_size_ok(ps, head_dim=128)
    assert not ops.paged_page_size_ok(16384, head_dim=128)


def test_decode_attention_ring_semantics_match_model():
    """Kernel + ring-validity mask == the model's decode_attention maths."""
    B, L, KV, dh, t = 2, 64, 2, 32, 100
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 4, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, L, KV, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, L, KV, dh), jnp.float32)
    idx = jnp.arange(L)
    k_pos = t - jnp.mod(t - idx, L)
    valid = (k_pos >= 0) & (k_pos <= t)
    out = ops.decode_attention(q, k, v, jnp.broadcast_to(valid, (B, L)),
                               block_l=32, interpret=True)
    expect = ref.decode_attention(q, k, v, jnp.broadcast_to(valid, (B, L)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------

PAGED_CASES = [
    # B, H, KV, dh, P, n_log, ps
    (2, 4, 2, 64, 16, 4, 16),
    (1, 8, 1, 128, 8, 8, 8),
    (3, 4, 4, 32, 12, 3, 32),
    (1, 16, 8, 128, 24, 2, 64),
]


def _paged_inputs(B, H, KV, dh, P, n, ps, dtype, key=KEY):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, dh), dtype)
    k_pages = jax.random.normal(ks[1], (P, ps, KV, dh), dtype)
    v_pages = jax.random.normal(ks[2], (P, ps, KV, dh), dtype)
    # Arbitrary page-table contents are legal: repeats (shared prefixes)
    # and page 0 (the engine's trash page) included.
    pages = jax.random.randint(ks[3], (B, n), 0, P)
    valid = jax.random.bernoulli(ks[4], 0.7, (B, n * ps)).at[:, 0].set(True)
    return q, k_pages, v_pages, pages, valid


@pytest.mark.parametrize("case", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_sweep(case, dtype):
    B, H, KV, dh, P, n, ps = case
    q, kp, vp, pages, valid = _paged_inputs(B, H, KV, dh, P, n, ps, dtype)
    out = ops.paged_decode_attention(q, kp, vp, pages, valid,
                                     impl="pallas", interpret=True)
    expect = ref.paged_decode_attention(q, kp, vp, pages, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


def test_paged_decode_matches_flat_gather():
    """Walking the page table block-by-block == gathering the rows' pages
    into a flat [B, L] ring and running the FLAT kernel on it."""
    B, H, KV, dh, P, n, ps = 3, 4, 2, 64, 10, 4, 16
    q, kp, vp, pages, valid = _paged_inputs(B, H, KV, dh, P, n, ps,
                                            jnp.float32)
    out = ops.paged_decode_attention(q, kp, vp, pages, valid,
                                     impl="pallas", interpret=True)
    k_flat = kp[pages].reshape(B, n * ps, KV, dh)
    v_flat = vp[pages].reshape(B, n * ps, KV, dh)
    flat = ops.decode_attention(q, k_flat, v_flat, valid, block_l=ps,
                                impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(flat),
                               rtol=1e-5, atol=1e-5)


def test_paged_decode_dispatch_paths_agree():
    """Pallas body (interpret) vs the jnp oracle through the SAME
    ``kernels.ops`` dispatcher, including the all-invalid row whose
    contract is zeros."""
    B, H, KV, dh, P, n, ps = 3, 4, 2, 64, 9, 3, 32
    q, kp, vp, pages, valid = _paged_inputs(B, H, KV, dh, P, n, ps,
                                            jnp.float32)
    valid = valid.at[0].set(True).at[1].set(False)   # full / empty / ragged
    out_pl = ops.paged_decode_attention(q, kp, vp, pages, valid,
                                        impl="pallas", interpret=True)
    out_ref = ops.paged_decode_attention(q, kp, vp, pages, valid, impl="ref")
    np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(out_pl[1]),
                                  np.zeros((H, dh), np.float32))


# ---------------------------------------------------------------------------
# rg-lru scan
# ---------------------------------------------------------------------------

RGLRU_CASES = [(2, 512, 256), (1, 256, 128), (4, 128, 384)]


@pytest.mark.parametrize("case", RGLRU_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_sweep(case, dtype):
    B, S, W = case
    ks = jax.random.split(KEY, 3)
    a = jax.random.uniform(ks[0], (B, S, W), jnp.float32, 0.8, 0.999).astype(dtype)
    x = jax.random.normal(ks[1], (B, S, W), dtype)
    h0 = jax.random.normal(ks[2], (B, W), jnp.float32)
    y, hl = ops.rglru_scan(a, x, h0, block_s=128, block_w=128, interpret=True)
    ye, hle = ref.rglru_scan(a, x, h0)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ye, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hle),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_rglru_matches_model_associative_scan():
    """Kernel == the model's associative-scan implementation."""
    from repro.models.rglru import rglru_scan as model_scan
    from repro import configs
    cfg = configs.get_reduced("recurrentgemma-2b")
    B, S, W = 2, 128, 128
    ks = jax.random.split(KEY, 3)
    a = jax.random.uniform(ks[0], (B, S, W), jnp.float32, 0.8, 0.999)
    x = jax.random.normal(ks[1], (B, S, W), jnp.float32)
    h0 = jnp.zeros((B, W), jnp.float32)
    y_k, h_k = ops.rglru_scan(a, x, h0, interpret=True)
    y_r, h_r = ref.rglru_scan(a, x, h0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------

SSM_CASES = [(2, 256, 256, 16), (1, 128, 128, 8), (2, 64, 384, 4)]


@pytest.mark.parametrize("case", SSM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_sweep(case, dtype):
    B, S, Di, N = case
    ks = jax.random.split(KEY, 6)
    u = jax.random.normal(ks[0], (B, S, Di), dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Di))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (Di, N)) * 0.5)
    Bc = jax.random.normal(ks[3], (B, S, N), dtype)
    Cc = jax.random.normal(ks[4], (B, S, N), dtype)
    D = jax.random.normal(ks[5], (Di,), jnp.float32)
    h0 = jnp.zeros((B, Di, N), jnp.float32)
    y, hl = ops.ssm_scan(u, delta, A, Bc, Cc, D, h0, block_s=64,
                         block_d=128, interpret=True)
    ye, hle = ref.ssm_scan(u, delta, A, Bc, Cc, D, h0)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ye, np.float32), **tol)


def test_ssm_scan_chunk_boundary_state_carry():
    """State must carry exactly across sequence-block boundaries."""
    B, S, Di, N = 1, 128, 128, 8
    ks = jax.random.split(KEY, 6)
    u = jax.random.normal(ks[0], (B, S, Di), jnp.float32)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (B, S, Di)))
    A = -jnp.exp(jax.random.normal(ks[2], (Di, N)) * 0.5)
    Bc = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cc = jax.random.normal(ks[4], (B, S, N), jnp.float32)
    D = jnp.zeros((Di,), jnp.float32)
    h0 = jax.random.normal(ks[5], (B, Di, N), jnp.float32)
    outs = [np.asarray(ops.ssm_scan(u, delta, A, Bc, Cc, D, h0,
                                    block_s=bs, block_d=64,
                                    interpret=True)[0])
            for bs in (32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)
