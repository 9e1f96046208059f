"""The serving copy of the weights (``transformer.serving_params``): which
leaves it casts to the compute dtype, that serving from it is
token-identical to decoding the float32 masters, and that a weight swap
casts once and reuses the warmed executables."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer
from repro.models.config import XATTN
from repro.serve import decode as serve_lib
from repro.serve.engine import ServeEngine

DECODE_ARCHS = [n for n in configs.ARCH_NAMES
                if configs.get_reduced(n).decode_supported]
# The engine admits a request by its token prompt alone; a cross-attention
# stack also needs frontend memory, so it is checked through decode_step.
ENGINE_ARCHS = [n for n in DECODE_ARCHS
                if XATTN not in configs.get_reduced(n).pattern]

# Every linear layer of every family: its kernel and bias are read only
# through layers.apply_linear, which casts them to the activations' dtype.
LINEARS = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "router",
           "head", "in_x", "in_gate", "out", "in_proj", "x_proj",
           "dt_proj", "out_proj", "wkv_a", "wkv_b"}
NORMS = {"norm", "mlp_norm", "final_norm", "q_norm", "k_norm"}
L = 24
MAX_NEW = 3
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def _masters(arch, seed=1):
    """Reduced float32 masters with every leaf off the bfloat16 grid:
    init puts norm scales at one and biases at zero, which a wrong cast
    would leave unchanged."""
    cfg = configs.get_reduced(arch)
    params = transformer.init_params(cfg, jax.random.key(seed))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1000), len(leaves))
    return cfg, jax.tree.unflatten(tree, [
        x + 0.02 * jax.random.normal(k, x.shape)
        for x, k in zip(leaves, keys)])


def _path(keys) -> tuple:
    return tuple(k.key for k in keys)


def _cast_at_every_use(path: tuple) -> bool:
    """The leaves every reader casts to the compute dtype: a linear's
    kernel and bias, the embedding table, and MoE expert stacks (bare
    [E, ...] arrays named like the dense MLP's linears)."""
    if path == ("embed", "tokens"):
        return True
    if path[-1] in ("kernel", "bias") and path[-2] in LINEARS:
        return True
    return path[-2] == "mlp" and path[-1] in LINEARS


def _prompts(cfg, lens=(5, 9), seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _run(engine, futs, max_steps=500):
    steps = 0
    while not all(f.done() for f in futs):
        engine.step()
        steps += 1
        assert steps < max_steps, "engine made no progress"


def _solo(cfg, params, prompt, context_len=L, max_new=MAX_NEW):
    return np.asarray(serve_lib.generate(
        cfg, params, jnp.asarray(prompt[None]), max_new=max_new,
        context_len=context_len))[0]


class _CompileCounter:
    """Backend compiles while armed, as the benchmark counts them."""

    def __init__(self):
        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == BACKEND_COMPILE:
            self.count += 1


@pytest.fixture(scope="module")
def compiles():
    return _CompileCounter()


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_serving_params_casts_exactly_the_point_of_use_leaves(arch):
    cfg, masters = _masters(arch)
    served = transformer.serving_params(cfg, masters)
    got = dict((_path(k), v) for k, v in
               jax.tree_util.tree_leaves_with_path(served))
    cast = []
    for keys, m in jax.tree_util.tree_leaves_with_path(masters):
        path = _path(keys)
        s = got.pop(path)
        assert m.dtype == jnp.float32, path
        if _cast_at_every_use(path):
            cast.append(path)
            assert s.dtype == jnp.bfloat16, path
            np.testing.assert_array_equal(
                np.asarray(s), np.asarray(m.astype(jnp.bfloat16)))
        else:
            assert s is m, path         # kept: the same float32 array
            if set(path) & NORMS:
                assert s.dtype == jnp.float32, path
    assert not got, f"leaves the masters lack: {sorted(got)}"
    assert ("embed", "tokens") in cast
    assert any(p[-1] == "kernel" for p in cast)


@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_serving_params_passes_compute_dtype_leaves_through(arch):
    """Masters already in the compute dtype come back as the same arrays,
    and a float32-compute configuration casts nothing."""
    cfg, masters = _masters(arch)
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), masters)
    f32_compute = dataclasses.replace(cfg, compute_dtype="float32")
    for tree, c in ((bf16, cfg), (masters, f32_compute)):
        out = transformer.serving_params(c, tree)
        assert jax.tree.structure(out) == jax.tree.structure(tree)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
            assert a is b


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_serving_copy_decodes_like_the_masters(arch):
    """Prefill and a decode step give bit-identical logits from the
    serving copy and from the float32 masters: no reader takes a cast
    leaf in float32."""
    cfg, masters = _masters(arch)
    served = transformer.serving_params(cfg, masters)
    tokens = jnp.asarray(_prompts(cfg, (7,))[0][None])
    memory = None
    if cfg.frontend_tokens:
        memory = 0.1 * jax.random.normal(
            jax.random.key(2), (1, cfg.frontend_tokens, cfg.d_model))

    @jax.jit
    def run(params):
        logits, state = transformer.prefill(cfg, params, tokens=tokens,
                                            memory=memory, context_len=16)
        step, _ = transformer.decode_step(
            cfg, params, state, tokens[:, -1:],
            jnp.full((1,), tokens.shape[1], jnp.int32))
        return logits, step

    for a, b in zip(run(masters), run(served)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ENGINE_ARCHS)
def test_engine_on_float32_masters_matches_generate(arch):
    """The engine, handed float32 masters, serves the tokens that
    ``serve.decode.generate`` decodes from the same masters, and keeps
    no float32 copy of a leaf it casts."""
    cfg, masters = _masters(arch)
    engine = ServeEngine(cfg, masters, num_slots=2, context_len=L,
                         max_new=MAX_NEW)
    prompts = _prompts(cfg)
    futs = [engine.submit(p) for p in prompts]
    _run(engine, futs)
    for p, f in zip(prompts, futs):
        np.testing.assert_array_equal(f.result(), _solo(cfg, masters, p))
    for keys, leaf in jax.tree_util.tree_leaves_with_path(engine._params):
        if _cast_at_every_use(_path(keys)):
            assert leaf.dtype == jnp.bfloat16, _path(keys)
    assert engine.stats()["param_casts"] == 1
    engine.stop()


def test_swap_of_float32_masters_casts_once_and_stays_warm(compiles):
    cfg, p0 = _masters("qwen2-1.5b", seed=0)
    _, p1 = _masters("qwen2-1.5b", seed=1)
    prompt = np.arange(1, 7, dtype=np.int32)
    engine = ServeEngine(cfg, p0, num_slots=2, context_len=32,
                         max_new=4).warmup()
    for _ in range(2):              # every executable a request reaches
        fut = engine.submit(prompt)
        _run(engine, [fut])
    out0 = fut.result()
    compiles.count, compiles.armed = 0, True
    try:
        engine.swap_params(p1, block=False)
        fut = engine.submit(prompt)
        _run(engine, [fut])
    finally:
        compiles.armed = False
    assert compiles.count == 0
    np.testing.assert_array_equal(fut.result(),
                                  _solo(cfg, p1, prompt, 32, 4))
    assert not np.array_equal(out0, fut.result())
    stats = engine.stats()
    assert (stats["param_casts"], stats["param_swaps"]) == (2, 1)
    engine.stop()


def test_engine_server_load_version_of_float32_masters(compiles, tmp_path):
    from repro.ckpt.checkpoint import ModelStore
    from repro.launch.serve import EngineServer
    cfg, p0 = _masters("qwen2-1.5b", seed=0)
    _, p1 = _masters("qwen2-1.5b", seed=1)
    store = ModelStore(str(tmp_path / "store"))
    for v, p in enumerate((p0, p1)):
        store.publish_version(v, p)
    server = EngineServer(cfg, max_new=4, num_slots=2, context_len=32,
                          store_dir=str(tmp_path / "store"), version=0)
    prompt = np.arange(1, 7, dtype=np.int32)
    try:
        for _ in range(2):
            out0 = np.asarray(server.generate(prompt))
        np.testing.assert_array_equal(out0, _solo(cfg, p0, prompt, 32, 4))
        server.load_version(1)
        compiles.count, compiles.armed = 0, True
        try:
            out1 = np.asarray(server.generate(prompt))
        finally:
            compiles.armed = False
        assert compiles.count == 0
        np.testing.assert_array_equal(out1, _solo(cfg, p1, prompt, 32, 4))
        assert server.stats()["param_casts"] == 2
    finally:
        server.kill()
