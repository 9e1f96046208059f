"""Hypothesis property tests on system invariants."""

import threading

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.courier import serialization as ser
from repro.core.fault import RestartPolicy
from repro.data.replay import ReplayServer, TableConfig

# ---------------------------------------------------------------------------
# Courier serialization: loads(dumps(x)) == x for transportable values
# ---------------------------------------------------------------------------

json_like = st.recursive(
    st.none() | st.booleans() | st.integers(-2**31, 2**31)
    | st.floats(allow_nan=False, allow_infinity=False) | st.text(max_size=20),
    lambda children: st.lists(children, max_size=4)
    | st.dictionaries(st.text(max_size=8), children, max_size=4),
    max_leaves=20)


@given(json_like)
@settings(max_examples=50, deadline=None)
def test_serialization_roundtrip(obj):
    out = ser.loads(ser.dumps(obj))
    assert out == obj or _tuplify(out) == _tuplify(obj)


def _tuplify(x):
    if isinstance(x, (list, tuple)):
        return tuple(_tuplify(v) for v in x)
    if isinstance(x, dict):
        return {k: _tuplify(v) for k, v in x.items()}
    return x


@given(st.lists(st.integers(1, 64), min_size=1, max_size=3),
       st.sampled_from([np.float32, np.int32, np.float16]))
@settings(max_examples=25, deadline=None)
def test_serialization_roundtrip_arrays(shape, dtype):
    arr = np.arange(int(np.prod(shape)), dtype=dtype).reshape(shape)
    out = ser.loads(ser.dumps({"x": arr}))["x"]
    np.testing.assert_array_equal(out, arr)
    assert out.dtype == arr.dtype


@given(json_like,
       st.lists(st.integers(1, 4096), min_size=0, max_size=4),
       st.integers(0, 64))
@settings(max_examples=50, deadline=None)
def test_scatter_gather_encode_matches_dumps(obj, arr_sizes, slack):
    """The shm ring's scatter-gather path (encode_frames + framed_size +
    write_framed_into) must produce byte-for-byte what ``dumps`` joins,
    for any payload and any buffer slack, and round-trip through loads."""
    payload = {"obj": obj,
               "arrays": [np.arange(n, dtype=np.float32) for n in arr_sizes]}
    frames = ser.encode_frames(payload)
    size = ser.framed_size(frames)
    buf = bytearray(size + slack)
    written = ser.write_framed_into(buf, frames)
    assert written == size
    assert bytes(buf[:written]) == ser.dumps(payload)
    out = ser.loads(bytes(buf[:written]))
    assert _tuplify(out["obj"]) == _tuplify(obj)
    for got, n in zip(out["arrays"], arr_sizes):
        np.testing.assert_array_equal(got, np.arange(n, dtype=np.float32))


@given(st.integers(0, 4096))
@settings(max_examples=20, deadline=None)
def test_write_framed_into_rejects_short_buffers(deficit):
    frames = ser.encode_frames({"x": np.zeros(1024, np.float32)})
    size = ser.framed_size(frames)
    if deficit == 0 or deficit > size:
        return
    with pytest.raises(ValueError, match="needs"):
        ser.write_framed_into(bytearray(size - deficit), frames)


# ---------------------------------------------------------------------------
# RestartPolicy invariants
# ---------------------------------------------------------------------------

@given(st.integers(0, 10), st.floats(0.001, 1.0), st.floats(1.0, 4.0),
       st.integers(0, 12))
@settings(max_examples=50, deadline=None)
def test_backoff_monotone_and_capped(max_restarts, base, mult, i):
    p = RestartPolicy(max_restarts=max_restarts, backoff_s=base,
                      backoff_multiplier=mult, max_backoff_s=5.0)
    b1, b2 = p.backoff_for(i), p.backoff_for(i + 1)
    assert 0 < b1 <= 5.0 and b1 <= b2 + 1e-9
    assert p.allows(i) == (i < max_restarts)


def test_negative_budget_always_allows():
    p = RestartPolicy(max_restarts=-1)
    assert all(p.allows(i) for i in (0, 10, 10_000))


# ---------------------------------------------------------------------------
# Replay invariants: size bound, SPI rate limiting, FIFO order
# ---------------------------------------------------------------------------

@given(st.integers(1, 50), st.integers(1, 120))
@settings(max_examples=25, deadline=None)
def test_replay_size_never_exceeds_max(max_size, n_inserts):
    rs = ReplayServer([TableConfig("t", max_size=max_size)])
    for i in range(n_inserts):
        assert rs.insert("t", i, timeout=1.0)
    assert rs.size("t") == min(max_size, n_inserts)


@given(st.integers(1, 30))
@settings(max_examples=20, deadline=None)
def test_replay_fifo_order(n):
    rs = ReplayServer([TableConfig("t", max_size=1000, sampler="fifo")])
    for i in range(n):
        rs.insert("t", i, timeout=1.0)
    out = rs.sample("t", n, timeout=1.0)
    assert out == list(range(n))


def test_replay_spi_blocks_oversampling():
    rs = ReplayServer([TableConfig(
        "t", max_size=100, samples_per_insert=2.0, spi_tolerance=1.0,
        min_size_to_sample=1)])
    rs.insert("t", 0, timeout=1.0)
    # budget = 2*1 + 2*1 = 4 samples
    assert rs.sample("t", 4, timeout=0.5) is not None
    assert rs.sample("t", 1, timeout=0.2) is None  # over budget -> timeout
    rs.insert("t", 1, timeout=1.0)
    assert rs.sample("t", 1, timeout=1.0) is not None  # unblocked


def test_replay_insert_blocks_when_too_far_ahead():
    rs = ReplayServer([TableConfig(
        "t", max_size=100, samples_per_insert=1.0, spi_tolerance=1.0,
        min_size_to_sample=1)])
    ok = [rs.insert("t", i, timeout=0.2) for i in range(10)]
    assert not all(ok)  # the writer hit the rate limiter


# ---------------------------------------------------------------------------
# Sharding rules invariants
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(1, 512), min_size=1, max_size=3))
@settings(max_examples=50, deadline=None)
def test_fit_spec_always_divisible(shape):
    import jax
    from jax.sharding import PartitionSpec
    from repro.sharding.rules import fit_spec
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    spec = fit_spec(mesh, shape, [("data", "model")] * len(shape))
    assert isinstance(spec, PartitionSpec)
    # every sharded dim is divisible by the axis product
    for dim, entry in zip(shape, spec):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else entry
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        assert dim % n == 0


# ---------------------------------------------------------------------------
# Decode-attention kernel vs oracle over random shapes and masks
# ---------------------------------------------------------------------------

@given(st.integers(1, 3),                        # batch
       st.sampled_from([(2, 1), (4, 2), (4, 4)]),  # (heads, kv heads)
       st.sampled_from([32, 64]),                # head dim
       st.sampled_from([64, 96, 128]),           # ring length
       st.floats(0.0, 1.0),                      # valid density
       st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_decode_attention_kernel_matches_oracle(B, hkv, dh, L, density,
                                                seed):
    """Both leaves of the ops.decode_attention dispatcher agree for any
    shape and any validity mask — including rows the density strategy
    drives to all-False, where the contract is zeros."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    H, KV = hkv
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, L, KV, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, L, KV, dh), jnp.float32)
    valid = jax.random.bernoulli(ks[3], density, (B, L))
    out_pl = np.asarray(ops.decode_attention(q, k, v, valid, block_l=32,
                                             impl="pallas", interpret=True))
    out_ref = np.asarray(ops.decode_attention(q, k, v, valid, impl="ref"))
    np.testing.assert_allclose(out_pl, out_ref, rtol=1e-5, atol=1e-5)
    dead = ~np.asarray(valid).any(axis=1)
    assert (out_pl[dead] == 0).all()


@given(st.integers(1, 3),                        # batch
       st.sampled_from([(2, 1), (4, 2), (4, 4)]),  # (heads, kv heads)
       st.sampled_from([32, 64]),                # head dim
       st.sampled_from([(6, 2, 16), (10, 4, 8), (5, 3, 32)]),  # (P, n, ps)
       st.floats(0.0, 1.0),                      # valid density
       st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_paged_decode_attention_kernel_matches_oracle(B, hkv, dh, geom,
                                                      density, seed):
    """The paged Pallas walk over an arbitrary page table — repeated
    pages, trash-page (0) entries, any validity mask — matches the
    gather-then-flat-attention oracle; all-invalid rows yield zeros."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    H, KV = hkv
    P, n, ps = geom
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (B, H, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (P, ps, KV, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (P, ps, KV, dh), jnp.float32)
    pages = jax.random.randint(ks[3], (B, n), 0, P)
    valid = jax.random.bernoulli(ks[4], density, (B, n * ps))
    out_pl = np.asarray(ops.paged_decode_attention(q, kp, vp, pages, valid,
                                                   impl="pallas",
                                                   interpret=True))
    out_ref = np.asarray(ops.paged_decode_attention(q, kp, vp, pages, valid,
                                                    impl="ref"))
    np.testing.assert_allclose(out_pl, out_ref, rtol=1e-5, atol=1e-5)
    dead = ~np.asarray(valid).any(axis=1)
    assert (out_pl[dead] == 0).all()


# ---------------------------------------------------------------------------
# Optimizer invariants
# ---------------------------------------------------------------------------

@given(st.floats(0.1, 10.0), st.integers(1, 8))
@settings(max_examples=20, deadline=None)
def test_grad_clipping_bounds_update_norm(scale, dim):
    import jax
    import jax.numpy as jnp
    from repro.train import optimizer as opt
    cfg = opt.OptimizerConfig(lr=1.0, warmup_steps=0, total_steps=10,
                              clip_norm=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros((dim,), jnp.float32)}
    grads = {"w": jnp.full((dim,), scale, jnp.float32)}
    state = opt.init_opt_state(params)
    _, _, metrics = opt.apply_updates(cfg, params, grads, state)
    assert float(metrics["grad_norm"]) == pytest.approx(
        scale * dim ** 0.5, rel=1e-4)
