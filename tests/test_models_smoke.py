"""Per-architecture smoke tests (brief requirement): instantiate a REDUCED
config of each assigned family and run one forward/train step on CPU,
asserting output shapes and no NaNs. Full configs are exercised only via
the dry-run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer
from repro.models.config import shape_applicability, ALL_SHAPES
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import TrainConfig, make_train_state, make_train_step

B, S = 2, 32


def _batch(cfg, key):
    batch = {}
    if cfg.family == "audio":
        batch["embeddings"] = jax.random.normal(key, (B, S, cfg.d_model),
                                                jnp.bfloat16)
        batch["targets"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        batch["mask"] = jnp.ones((B, S), jnp.float32)
    else:
        toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        batch["tokens"] = toks
        batch["labels"] = toks
        if cfg.family == "vlm":
            batch["image_embeds"] = jax.random.normal(
                key, (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_forward_shapes_and_finite(arch):
    cfg = configs.get_reduced(arch)
    key = jax.random.key(0)
    params = transformer.init_params(cfg, key)
    batch = _batch(cfg, key)
    hidden, aux = transformer.forward(
        cfg, params, tokens=batch.get("tokens"),
        embeddings=batch.get("embeddings"),
        memory=batch.get("image_embeds"))
    assert hidden.shape == (B, S, cfg.d_model)
    logits = transformer.logits_from_hidden(cfg, params, hidden)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_one_train_step(arch):
    cfg = configs.get_reduced(arch)
    key = jax.random.key(1)
    step = jax.jit(make_train_step(cfg, TrainConfig(
        optimizer=OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=10),
        num_microbatches=2)))
    params, opt = make_train_state(cfg, key)
    batch = jax.tree.map(jnp.asarray, _batch(cfg, key))
    params2, opt2, metrics = step(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(opt2["step"]) == 1
    # params actually moved
    moved = any(
        float(jnp.abs(a - b).max()) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved


@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_decode_step_or_skip(arch):
    cfg = configs.get_reduced(arch)
    if not cfg.decode_supported:
        pytest.skip("encoder-only: no decode step")
    key = jax.random.key(2)
    params = transformer.init_params(cfg, key)
    state = transformer.init_decode_state(cfg, B, 64)
    toks = jax.random.randint(key, (B, 1), 0, cfg.vocab_size)
    logits, state2 = transformer.decode_step(cfg, params, state, toks,
                                             jnp.int32(3))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert jax.tree.structure(state) == jax.tree.structure(state2)


@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_prefill_matches_forward(arch):
    cfg = configs.get_reduced(arch)
    if not cfg.decode_supported:
        pytest.skip("encoder-only: prefill == forward by construction")
    key = jax.random.key(3)
    params = transformer.init_params(cfg, key)
    batch = _batch(cfg, key)
    hidden, _ = transformer.forward(cfg, params, tokens=batch.get("tokens"),
                                    memory=batch.get("image_embeds"))
    logits_fwd = transformer.logits_from_hidden(cfg, params, hidden)
    logits_pf, state = transformer.prefill(
        cfg, params, tokens=batch.get("tokens"),
        memory=batch.get("image_embeds"), context_len=64)
    np.testing.assert_allclose(np.asarray(logits_fwd, np.float32),
                               np.asarray(logits_pf, np.float32),
                               rtol=2e-2, atol=2e-2)
    assert state


def test_param_counts_match_published():
    known = {
        "hubert-xlarge": 0.96e9, "qwen2-1.5b": 1.54e9,
        "command-r-plus-104b": 104e9, "starcoder2-3b": 3.0e9,
        "qwen3-8b": 8.2e9, "llama-3.2-vision-11b": 9.8e9,
        "mixtral-8x22b": 141e9, "mixtral-8x7b": 46.7e9,
        "recurrentgemma-2b": 2.7e9, "falcon-mamba-7b": 7.3e9,
    }
    for arch, expect in known.items():
        n = configs.get(arch).param_count()
        assert abs(n - expect) / expect < 0.08, (arch, n, expect)


def test_mixtral_active_params():
    cfg = configs.get("mixtral-8x22b")
    assert abs(cfg.active_param_count() - 39e9) / 39e9 < 0.05


def test_shape_applicability_matrix():
    rows = {(a, s.name): shape_applicability(configs.get(a), s)
            for a in configs.ARCH_NAMES for s in ALL_SHAPES}
    # hubert: no decode shapes
    assert rows[("hubert-xlarge", "decode_32k")] is not None
    assert rows[("hubert-xlarge", "long_500k")] is not None
    # full-attention archs skip long_500k
    for a in ("qwen2-1.5b", "qwen3-8b", "command-r-plus-104b",
              "llama-3.2-vision-11b", "deepseek-v2-lite"):
        assert rows[(a, "long_500k")] is not None
    # sub-quadratic archs run long_500k
    for a in ("falcon-mamba-7b", "recurrentgemma-2b", "mixtral-8x7b",
              "mixtral-8x22b", "starcoder2-3b"):
        assert rows[(a, "long_500k")] is None
    # 37 runnable cells, 7 structurally inapplicable
    runnable = sum(1 for v in rows.values() if v is None)
    assert runnable == 37
