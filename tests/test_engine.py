"""ServeEngine invariants: slotted-KV-cache admission, retirement, and
per-request delivery semantics (continuous batching)."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from repro import configs
from repro.models import transformer
from repro.serve import decode as serve_lib
from repro.serve.engine import ServeEngine

CFG = configs.get_reduced("qwen2-1.5b")
L = 24          # engine context (slot ring length)
MAX_NEW = 4


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
            for n in lens]


def _run(engine, futs, max_steps=500):
    steps = 0
    while not all(f.done() for f in futs):
        engine.step()
        steps += 1
        assert steps < max_steps, "engine made no progress"


def _solo(params, prompt, max_new=MAX_NEW):
    import jax.numpy as jnp
    return np.asarray(serve_lib.generate(
        CFG, params, jnp.asarray(prompt[None]), max_new=max_new,
        context_len=L))[0]


def test_engine_matches_solo_serving(params):
    """A request decoded in a shared slot pool must equal the same prompt
    served alone (same ring length): slots are isolated."""
    engine = ServeEngine(CFG, params, num_slots=3, context_len=L,
                         max_new=MAX_NEW)
    prompts = _prompts([5, 9, 7, 5, 12])
    futs = [engine.submit(p) for p in prompts]
    _run(engine, futs)
    for p, f in zip(prompts, futs):
        out = f.result()
        assert out.shape == (len(p) + MAX_NEW,)
        np.testing.assert_array_equal(out, _solo(params, p))


@pytest.mark.parametrize("arch", ["recurrentgemma-2b", "falcon-mamba-7b"])
def test_engine_serves_recurrent_archs(arch):
    """Exact-length admission keeps recurrent state (RG-LRU / Mamba)
    correct — no pad tokens ever enter a prefill."""
    cfg = configs.get_reduced(arch)
    params = transformer.init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9)]
    engine = ServeEngine(cfg, params, num_slots=2, context_len=L,
                         max_new=3)
    futs = [engine.submit(p) for p in prompts]
    _run(engine, futs)
    import jax.numpy as jnp
    for p, f in zip(prompts, futs):
        solo = np.asarray(serve_lib.generate(
            cfg, params, jnp.asarray(p[None]), max_new=3,
            context_len=L))[0]
        np.testing.assert_array_equal(f.result(), solo)


def test_slot_reuse_and_full_pool_queues(params):
    """More requests than slots: the pool queues (never errors), retired
    slots are reused, and occupancy never exceeds the pool."""
    engine = ServeEngine(CFG, params, num_slots=2, context_len=L,
                         max_new=MAX_NEW)
    prompts = _prompts([5] * 7, seed=2)
    futs = [engine.submit(p) for p in prompts]
    assert engine.stats()["queue_depth"] == 7     # nothing admitted yet
    _run(engine, futs)
    for p, f in zip(prompts, futs):
        assert f.result().shape == (len(p) + MAX_NEW,)
    s = engine.stats()
    assert s["admitted"] == 7
    assert s["retired"] == 7
    assert s["peak_occupancy"] <= 2
    assert s["free_slots"] == 2
    assert s["queue_depth"] == 0


def test_interleaved_admission_preserves_inflight_decode(params):
    """Admitting B mid-flight (prefill + slot write between decode steps)
    must not perturb A's in-flight rows, and vice versa."""
    a, b = _prompts([6, 10], seed=3)
    engine = ServeEngine(CFG, params, num_slots=2, context_len=L,
                         max_new=MAX_NEW)
    fa = engine.submit(a)
    engine.step()
    engine.step()                                 # A is mid-decode
    fb = engine.submit(b)
    _run(engine, [fa, fb])
    np.testing.assert_array_equal(fa.result(), _solo(params, a))
    np.testing.assert_array_equal(fb.result(), _solo(params, b))


def test_per_request_failure_delivery(params):
    """A request that cannot fit fails its own future; neighbours in the
    same step complete untouched."""
    engine = ServeEngine(CFG, params, num_slots=2, context_len=L,
                         max_new=MAX_NEW)
    good1 = engine.submit(_prompts([5], seed=4)[0])
    bad = engine.submit(np.arange(L, dtype=np.int32))   # L + max_new > L
    good2 = engine.submit(_prompts([7], seed=5)[0])
    with pytest.raises(ValueError, match="context_len"):
        bad.result(timeout=5)
    _run(engine, [good1, good2])
    assert good1.result().shape == (5 + MAX_NEW,)
    assert good2.result().shape == (7 + MAX_NEW,)
    assert engine.stats()["failed"] == 0          # rejected pre-queue
    assert engine.stats()["retired"] == 2


def test_eos_retires_slot_immediately(params):
    """EOS retirement: with eos_id set to the token the model actually
    emits first, the sequence retires after one generated token and its
    slot frees for the next request."""
    prompt = _prompts([6], seed=6)[0]
    probe = ServeEngine(CFG, params, num_slots=1, context_len=L,
                        max_new=MAX_NEW)
    f = probe.submit(prompt)
    _run(probe, [f])
    first_tok = int(f.result()[len(prompt)])

    engine = ServeEngine(CFG, params, num_slots=1, context_len=L,
                         max_new=MAX_NEW, eos_id=first_tok)
    f1 = engine.submit(prompt)
    f2 = engine.submit(_prompts([9], seed=7)[0])
    _run(engine, [f1, f2])
    out = f1.result()
    assert out.shape == (len(prompt) + 1,)        # stopped at EOS
    assert out[-1] == first_tok
    s = engine.stats()
    assert s["retired"] == 2 and s["free_slots"] == 1


def test_stop_fails_pending_requests(params):
    engine = ServeEngine(CFG, params, num_slots=1, context_len=L,
                         max_new=MAX_NEW)
    fut = engine.submit(_prompts([5], seed=8)[0])
    engine.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        fut.result(timeout=5)
    with pytest.raises(RuntimeError, match="stopped"):
        engine.submit(_prompts([5], seed=9)[0]).result(timeout=5)


def test_background_loop_serves(params):
    """The daemon decode loop: submit from this thread, replies stream
    back per request through the futures."""
    with ServeEngine(CFG, params, num_slots=2, context_len=L,
                     max_new=MAX_NEW) as engine:
        prompts = _prompts([5, 8, 11], seed=10)
        futs = [engine.submit(p) for p in prompts]
        for p, f in zip(prompts, futs):
            assert f.result(timeout=120).shape == (len(p) + MAX_NEW,)
    assert engine.stats()["retired"] == 3


@pytest.mark.parametrize("warmup", [False, True])
def test_decode_failure_fails_waiters_fast(params, warmup):
    """A decode window that raises in the daemon loop (a compile error, a
    device fault) — while serving, or in the loop's warm-up — fails every
    in-flight and queued request with that exception at once, not after
    the caller's timeout, and stops the engine."""
    engine = ServeEngine(CFG, params, num_slots=1, context_len=L,
                         max_new=MAX_NEW)
    boom = RuntimeError("injected decode failure")

    def broken_window(steps):
        def run(*args, **kwargs):
            raise boom
        return run

    engine._fused = broken_window
    prompts = _prompts([5, 6, 7], seed=11)
    futs = [engine.submit(p) for p in prompts]   # queued before the loop
    t0 = time.monotonic()
    engine.start(warmup=warmup)
    for f in futs:
        with pytest.raises(RuntimeError, match="injected decode failure"):
            f.result(timeout=30)
    assert time.monotonic() - t0 < 30
    assert not engine.alive
    with pytest.raises(RuntimeError, match="injected decode failure"):
        engine.submit(prompts[0]).result(timeout=5)
    assert engine.stats()["free_slots"] == 1
    engine.stop()


def test_page_size_the_kernel_cannot_tile_is_refused_on_tpu(params,
                                                            monkeypatch):
    """On TPU, a page size whose K/V blocks overflow the paged kernel's
    VMEM is refused when the engine is built, not at the first decode."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="page_size 131072 cannot be tiled"):
        ServeEngine(CFG, params, num_slots=2, context_len=L,
                    max_new=MAX_NEW, page_size=131072)
    # The dense path never runs the kernel, and a tileable page is fine.
    ServeEngine(CFG, params, num_slots=2, context_len=L, max_new=MAX_NEW,
                page_size=131072, num_pages=2, decode_impl="dense")
    ServeEngine(CFG, params, num_slots=2, context_len=L, max_new=MAX_NEW,
                page_size=8)


def test_flat_ring_is_allocated_at_a_length_the_kernel_tiles(params):
    """The flat KV rings are allocated at ``decode_cache_len(context_len)``,
    so the flash-decode wrapper never pads (copies) the cache on a decode
    step; the dense path keeps ``context_len``. Requests are still held to
    ``context_len`` and decode as they would alone."""
    from repro.kernels import ops

    def shapes(tree):
        return [x.shape for x in jax.tree.leaves(tree)]

    def spec(length):
        return shapes(transformer.decode_state_spec(CFG, 2, length))

    ctx = 1032
    flash = ServeEngine(CFG, params, num_slots=2, context_len=ctx,
                        max_new=MAX_NEW, decode_impl="flash")
    assert shapes(flash._state) == spec(ops.decode_cache_len(ctx)) != spec(ctx)
    dense = ServeEngine(CFG, params, num_slots=2, context_len=ctx,
                        max_new=MAX_NEW, decode_impl="dense")
    assert shapes(dense._state) == spec(ctx)
    with pytest.raises(ValueError, match="exceeds"):
        flash.submit(np.zeros(ctx - MAX_NEW + 1, np.int32)).result(timeout=5)
    prompt = _prompts([6])[0]
    fut = flash.submit(prompt)
    _run(flash, [fut])
    np.testing.assert_array_equal(fut.result(), _solo(params, prompt))


_ON_DEVICE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.models import transformer
from repro.serve import decode as serve_lib
from repro.serve.engine import ServeEngine
cfg = configs.get_reduced("qwen2-1.5b")
d0, d1 = jax.devices()
params = transformer.init_params(cfg, jax.random.key(0))
prompt = np.arange(3, 9, dtype=np.int32)
solo = np.asarray(serve_lib.generate(cfg, params, jnp.asarray(prompt[None]),
                                     max_new=4, context_len=24))[0]
for page_size in (None, 8):
    eng = ServeEngine(cfg, jax.device_put(params, d1), num_slots=2,
                      context_len=24, max_new=4, page_size=page_size)
    def on_d1():
        leaves = jax.tree.leaves((eng._params, eng._state, eng._tokens_dev,
                                  eng._t_dev))
        return all(x.devices() == {d1} for x in leaves)
    assert on_d1(), "engine state not on its device"
    with eng.warmup():
        out = eng.submit(prompt).result(timeout=120)
        eng.swap_params(jax.device_put(params, d0))
    assert on_d1(), "decode or a weight swap moved state off the device"
    np.testing.assert_array_equal(out, solo)
print("OK")
"""


def test_engine_lives_on_its_device():
    """Params on one device pin the decode state and the device-resident
    feed state there too (one replica per chip), through warmup and
    serving, on both KV layouts — and serve the same tokens."""
    env = {k: os.environ[k] for k in ("PATH", "HOME", "TMPDIR")
           if k in os.environ}
    env.update(PYTHONPATH="src", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _ON_DEVICE],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip().endswith("OK")


# -- roofline decode path: fused windows, kernel dispatch, chunked prefill ----

@pytest.mark.parametrize("arch", ["qwen2-1.5b", "recurrentgemma-2b"])
@pytest.mark.parametrize("sync_every", [1, 8])
@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_fused_and_flash_match_solo(arch, sync_every, impl):
    """The whole roofline matrix — {dense, flash kernel dispatch} x
    {sync every step, fused 8-step windows} x {attention-only,
    recurrent} — must be token-identical to solo decoding: the fused
    scan body IS the single-step path, and the kernel is an exact
    drop-in for the dense ring attention."""
    cfg = configs.get_reduced(arch)
    params = transformer.init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 12)]
    engine = ServeEngine(cfg, params, num_slots=2, context_len=L,
                         max_new=MAX_NEW, sync_every=sync_every,
                         decode_impl=impl)
    futs = [engine.submit(p) for p in prompts]
    _run(engine, futs)
    import jax.numpy as jnp
    for p, f in zip(prompts, futs):
        solo = np.asarray(serve_lib.generate(
            cfg, params, jnp.asarray(p[None]), max_new=MAX_NEW,
            context_len=L, attn_impl=impl))[0]
        np.testing.assert_array_equal(f.result(), solo)


def test_chunked_prefill_matches_solo(params):
    """Chunked admission (prefill_chunk=4): prompts longer than one chunk
    stream through ``prefill_extend`` between decode steps — including a
    length that is an exact multiple of the chunk and one short enough to
    stay monolithic — and every sequence still equals solo decoding."""
    engine = ServeEngine(CFG, params, num_slots=3, context_len=L,
                         max_new=MAX_NEW, prefill_chunk=4)
    prompts = _prompts([3, 8, 9, 14, 6], seed=12)
    futs = [engine.submit(p) for p in prompts]
    _run(engine, futs)
    for p, f in zip(prompts, futs):
        np.testing.assert_array_equal(f.result(), _solo(params, p))
    s = engine.stats()
    assert s["admitted"] == 5 and s["retired"] == 5
    assert s["free_slots"] == 3                   # no slot leaked by chunking


def test_fused_windows_batch_host_syncs(params):
    """sync_every=8 must actually batch syncs: at max_new=8 the engine
    should sync once per multi-token window plus once per admission —
    far below one sync per generated token."""
    engine = ServeEngine(CFG, params, num_slots=4, context_len=L,
                         max_new=8, sync_every=8).warmup()
    futs = [engine.submit(p, max_new=8) for p in _prompts([5, 7, 6, 9],
                                                          seed=13)]
    _run(engine, futs)
    s = engine.stats()
    assert s["generated_tokens"] == 32
    assert s["host_syncs"] < s["generated_tokens"] / 2
    assert s["syncs_per_token"] <= 0.3


def test_fused_sampling_is_sync_invariant(params):
    """Temperature/top-k sampling carries the PRNG key as device state
    through the fused windows: the same seed must yield the same tokens
    whether the engine syncs every step or every 8."""
    outs = []
    for sync in (1, 8):
        engine = ServeEngine(CFG, params, num_slots=4, context_len=L,
                             max_new=MAX_NEW, temperature=0.7, top_k=5,
                             seed=42, sync_every=sync)
        futs = [engine.submit(p) for p in _prompts([5, 8, 6], seed=14)]
        _run(engine, futs)
        outs.append([f.result() for f in futs])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


# -- paged KV pool + shared-prefix reuse --------------------------------------

@pytest.mark.parametrize("arch", ["qwen2-1.5b", "recurrentgemma-2b"])
@pytest.mark.parametrize("sync_every", [1, 8])
def test_paged_engine_matches_solo(arch, sync_every):
    """Paged pool (page_size=8) serves token-identically to solo decoding
    for the attention stack (paged rings + page-table walk) AND the
    recurrent stack (no full-context layer to page: the knobs are
    accepted and the flat per-row layout runs underneath)."""
    cfg = configs.get_reduced(arch)
    params = transformer.init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 12, 7)]
    engine = ServeEngine(cfg, params, num_slots=2, context_len=L,
                         max_new=MAX_NEW, sync_every=sync_every,
                         page_size=8, num_pages=12)
    futs = [engine.submit(p) for p in prompts]
    _run(engine, futs)
    import jax.numpy as jnp
    for p, f in zip(prompts, futs):
        solo = np.asarray(serve_lib.generate(
            cfg, params, jnp.asarray(p[None]), max_new=MAX_NEW,
            context_len=L))[0]
        np.testing.assert_array_equal(f.result(), solo)


def test_prefix_cache_reuse_matches_cold_prefill(params):
    """Prompts sharing a cached page-aligned prefix skip that prefix's
    prefill (prefix_tokens_reused > 0, cache hits) and still decode
    bit-identically to solo serving from a cold cache."""
    ps = 4
    rng = np.random.default_rng(22)
    shared = rng.integers(0, CFG.vocab_size, 12).astype(np.int32)
    tails = [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
             for n in (3, 5, 2)]
    prompts = [np.concatenate([shared, t]) for t in tails]
    engine = ServeEngine(CFG, params, num_slots=2, context_len=L,
                         max_new=MAX_NEW, page_size=ps, num_pages=16)
    f0 = engine.submit(prompts[0])    # cold: registers the shared pages
    _run(engine, [f0])
    futs = [engine.submit(p) for p in prompts[1:]]
    _run(engine, futs)
    for p, f in zip(prompts, [f0] + futs):
        np.testing.assert_array_equal(f.result(), _solo(params, p))
    s = engine.stats()
    assert s["prefix_cache"]["hits"] >= 2         # both warm prompts hit
    assert s["prefix_tokens_reused"] >= 2 * (12 // ps) * ps


def test_prefix_pages_released_on_retirement(params):
    """Retirement releases the rows' page refs immediately; pages that
    stay resident are exactly the prefix-cache entries' chains, and
    draining the cache makes the pool whole again."""
    engine = ServeEngine(CFG, params, num_slots=2, context_len=L,
                         max_new=MAX_NEW, page_size=4, num_pages=16)
    futs = [engine.submit(p) for p in _prompts([10, 13], seed=23)]
    _run(engine, futs)
    s = engine.stats()
    assert s["free_slots"] == 2                   # all rows retired
    held = {pid for chain in engine._prefix._entries.values()
            for pid in chain}
    assert s["pages_in_use"] == len(held)         # cache is the only holder
    while engine._prefix.evict_one(engine._decref):
        pass
    s = engine.stats()
    assert s["pages_free"] == s["pages_total"]


def test_prefix_cache_evicts_under_pool_pressure(params):
    """A pool too small to keep every retired prompt's prefix cached:
    admission evicts LRU refcount-zero entries instead of deadlocking,
    everything completes, and results stay exact."""
    engine = ServeEngine(CFG, params, num_slots=2, context_len=L,
                         max_new=MAX_NEW, page_size=4, num_pages=8)
    rng = np.random.default_rng(24)
    prompts = [rng.integers(0, CFG.vocab_size, 12).astype(np.int32)
               for _ in range(6)]
    futs = [engine.submit(p) for p in prompts]
    _run(engine, futs)
    for p, f in zip(prompts, futs):
        np.testing.assert_array_equal(f.result(), _solo(params, p))
    s = engine.stats()
    assert s["retired"] == 6
    assert s["prefix_cache"]["evictions"] >= 1


def test_request_exceeding_page_pool_fails_fast(params):
    """A request whose page budget can never be satisfied fails its own
    future at submit time (like the context_len check) instead of
    blocking admission forever."""
    engine = ServeEngine(CFG, params, num_slots=2, context_len=L,
                         max_new=MAX_NEW, page_size=4, num_pages=2)
    fut = engine.submit(np.arange(12, dtype=np.int32))    # needs 4 pages
    with pytest.raises(ValueError, match="pages"):
        fut.result(timeout=5)
    ok = engine.submit(_prompts([3], seed=25)[0])         # 2 pages: fits
    _run(engine, [ok])
    assert ok.result().shape == (3 + MAX_NEW,)


def test_paged_chunked_prefill_matches_solo(params):
    """Chunked admission against a paged pool: the B=1 chunk state lands
    through the copy-on-write scatter (start_page skips shared pages)
    and every sequence equals solo decoding."""
    engine = ServeEngine(CFG, params, num_slots=2, context_len=L,
                         max_new=MAX_NEW, prefill_chunk=4,
                         page_size=8, num_pages=9)
    prompts = _prompts([3, 9, 14, 6], seed=25)
    futs = [engine.submit(p) for p in prompts]
    _run(engine, futs)
    for p, f in zip(prompts, futs):
        np.testing.assert_array_equal(f.result(), _solo(params, p))
    s = engine.stats()
    assert s["free_slots"] == 2                   # no slot or page leaked
    assert s["pages_in_use"] == len(
        {pid for chain in engine._prefix._entries.values() for pid in chain})


def test_warmup_precompiles_paged_and_chunk_executables(params):
    """warmup() compiles the paged fused-window ladder and the
    chunk-shaped prefill_extend without touching live state; serving
    afterwards is exact."""
    engine = ServeEngine(CFG, params, num_slots=2, context_len=L,
                         max_new=MAX_NEW, prefill_chunk=4,
                         page_size=8, num_pages=9).warmup()
    futs = [engine.submit(p) for p in _prompts([9, 5], seed=26)]
    _run(engine, futs)
    for p, f in zip(_prompts([9, 5], seed=26), futs):
        np.testing.assert_array_equal(f.result(), _solo(params, p))
