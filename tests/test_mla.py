"""Latent attention (MLA) and the serving expert layer against plain
references, at a tiny size on the CPU.

The plain reference is the benchmark's own, ``bench/reference_mla.py``
(float32, ``Precision.HIGHEST``, published non-absorbed form), fed the
weights of ``bench/weights_mla.py``; it is checked in turn against the
``transformers`` port of DeepSeek-V2 where that is installed.

Tolerances and why:

* program against reference, in logits, computing in float32: 0.05. The
  cache holds its latent entries in bfloat16, as served: one rounding of
  each entry (a relative step of 2^-8) moves logits of order 1–3 by
  0.01–0.02 here; prefill alone agrees to 1e-5.
* served tokens against the reference, computing in bfloat16: the
  reference's logit of each served token within 0.1 of its best (the
  harness's ``logit_gap``). Bfloat16 activations and weights move logits
  by up to ~0.14 here, a router's top-k flip included, but rarely turn
  the largest; the float8 control of the same reference misses by 0.24
  and more at this size, so the bound still tells the two apart.
* float32 against float32 (reference against ``transformers``, the
  kernel's interpret run against its jnp oracle in float32, the expert
  shares against the uncut layer): 2e-4 to 1e-5, the rounding of float32
  sums whose terms are summed in another order.
* the kernel in bfloat16 against its oracle: 2e-2, one bfloat16 rounding
  of the probabilities before the value sum.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference_mla, weights_mla
from repro import configs
from repro.kernels import ops
from repro.models import mla, moe, transformer
from repro.models.config import ModelConfig

TOL = 0.05          # logits, float32 compute, bfloat16 cache
GAP = 0.1           # served tokens, bfloat16 compute


def _cfg(**kw) -> ModelConfig:
    """A three-layer decoder: a dense first layer, then MoE layers holding
    experts 4-7 of 16."""
    base = dataclasses.replace(
        configs.get_reduced("deepseek-v2-lite"), vocab_size=256,
        num_experts=16, experts_per_token=4, experts_held=4,
        expert_offset=4, rope_original_len=32)
    return dataclasses.replace(base, **kw)


def _m(cfg: ModelConfig) -> dict:
    return dataclasses.asdict(cfg)


def _params(cfg: ModelConfig, seed: int = 3, dtype: str = "float32"):
    return weights_mla.make(_m(cfg), seed, dtype)


def _ref_logits(cfg, params, seq: np.ndarray) -> np.ndarray:
    """Reference logits at every position of ``seq``."""
    S = -(-len(seq) // reference_mla.Q_BLOCK) * reference_mla.Q_BLOCK
    toks = np.zeros(S, np.int32)
    toks[:len(seq)] = seq
    out = reference_mla.logits_at(_m(cfg), params, jnp.asarray(toks),
                                  jnp.arange(len(seq), dtype=jnp.int32))
    return np.asarray(out)


def test_weights_layout_is_the_programs():
    cfg = _cfg()
    want = jax.tree.map(lambda a: a.shape,
                        transformer.init_params(cfg, jax.random.key(0)))
    got = jax.tree.map(lambda a: a.shape, _params(cfg))
    assert got == want


def test_paged_prefill_and_in_place_decode_match_reference():
    """Chunked prefill into a flat state, landed in the page pool behind a
    page table that shares one page with another row, then decode steps
    that read and write the pool in place: every step's logits agree with
    the reference's full forward pass."""
    cfg = _cfg(compute_dtype="float32")
    params = transformer.serving_params(cfg, _params(cfg))
    rng = np.random.default_rng(0)
    seq = rng.integers(0, cfg.vocab_size, 40, dtype=np.int32)
    ref = _ref_logits(cfg, _params(cfg), seq)
    ps, n_log, P0 = 8, 8, 1 + 16
    state = transformer.init_decode_state(cfg, 2, n_log * ps, page_size=ps,
                                          num_pages=P0)
    row_pages = np.arange(1, 1 + n_log, dtype=np.int32)
    flat = transformer.init_decode_state(cfg, 1, n_log * ps)
    P = 29
    for c0 in range(0, P, 12):
        c1 = min(c0 + 12, P)
        logits, flat = transformer.prefill_extend(
            cfg, params, flat, jnp.asarray(seq[None, c0:c1]), jnp.int32(c0))
        np.testing.assert_allclose(np.asarray(logits[0, 0], np.float32),
                                   ref[c1 - 1], atol=TOL)
    state = transformer.write_paged_slot(cfg, state, flat, 0,
                                         jnp.asarray(row_pages), 0, ps)
    # Row 1 shares row 0's first page (a cached prefix, fully covered by
    # its prompt) and owns the rest; it decodes past that page.
    pages = np.stack([row_pages, np.r_[row_pages[:1],
                                       np.arange(9, 16, dtype=np.int32)]])
    toks = np.stack([seq[P:P + 1], seq[:1]])
    t = np.array([P, ps + 2], np.int32)
    for i in range(8):
        logits, state = transformer.decode_step(
            cfg, params, state, jnp.asarray(toks), jnp.asarray(t),
            pages=jnp.asarray(pages))
        np.testing.assert_allclose(np.asarray(logits[0, 0], np.float32),
                                   ref[P + i], atol=TOL)
        toks = np.stack([seq[P + i + 1:P + i + 2], seq[:1]])
        t = t + 1
    # Row 1 only ever wrote its own page: the shared page is row 0's.
    flat_again = transformer.gather_paged_slot(cfg, state, 0,
                                               jnp.asarray(row_pages), ps)
    np.testing.assert_array_equal(
        np.asarray(flat_again["lead"]["0"]["ckv"][0, :ps]),
        np.asarray(flat["lead"]["0"]["ckv"][0, :ps]))


def test_engine_serves_reference_tokens():
    """Through ServeEngine: paged latent pool, chunked prefill, a prefix
    hit and fused windows of K > 1. Every served token's reference logit
    lies within GAP of the reference's best at its position."""
    from repro.serve.engine import ServeEngine
    cfg = _cfg()
    masters = _params(cfg)
    eng = ServeEngine(cfg, masters, num_slots=4, context_len=96,
                      page_size=8, prefill_chunk=16, prefix_cache=True,
                      sync_every=4, max_new=10)
    rng = np.random.default_rng(1)
    doc = rng.integers(0, cfg.vocab_size, 40, dtype=np.int32)
    prompts = [np.r_[doc, rng.integers(0, cfg.vocab_size, n,
                                       dtype=np.int32)] for n in (5, 9, 3)]
    prompts.append(rng.integers(0, cfg.vocab_size, 7, dtype=np.int32))
    futs = [eng.submit(prompts[0], max_new=6)]
    while not futs[0].done():
        eng.step()
    futs += [eng.submit(p, max_new=m) for p, m in zip(prompts[1:],
                                                      (10, 9, 8))]
    while not all(f.done() for f in futs):
        eng.step()
    stats = eng.stats()
    assert stats["prefix_tokens_reused"] >= 2 * 40 // 8 * 8
    assert stats["host_syncs"] < stats["generated_tokens"]   # K > 1 ran
    for p, f in zip(prompts, futs):
        out = f.result()
        ref = _ref_logits(cfg, masters, out)
        served = out[len(p):]
        rows = ref[len(p) - 1:len(out) - 1]
        gap = rows.max(-1) - rows[np.arange(len(served)), served]
        assert gap.max() <= GAP, gap


def test_expert_share_routes_each_row_alone():
    """The serving expert layer drops nothing and couples no rows: a row's
    output in a batch equals its output alone."""
    cfg = _cfg(compute_dtype="float32")
    p = moe.init_expert_share(cfg, jax.random.key(4))
    x = jax.random.normal(jax.random.key(5), (6, 3, cfg.d_model))
    y, pairs = moe.apply_expert_share(cfg, p, x)
    for b in range(6):
        yb, pb = moe.apply_expert_share(cfg, p, x[b:b + 1])
        np.testing.assert_allclose(np.asarray(yb[0]), np.asarray(y[b]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(pb[0]),
                                      np.asarray(pairs[b]))
    gates = moe.expert_gates(dataclasses.replace(cfg, experts_held=16,
                                                 expert_offset=0),
                             p["router"], x)
    held = (gates[..., 4:8] > 0).sum(-1)
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(held))


def test_held_shares_sum_to_the_uncut_layer():
    """Each of four replicas holds 4 of the 16 experts: the parts their
    held experts give, with the shared expert counted once, add up to the
    uncut layer."""
    full = _cfg(compute_dtype="float32", experts_held=16, expert_offset=0)
    p = moe.init_expert_share(full, jax.random.key(6))
    x = jax.random.normal(jax.random.key(7), (2, 5, full.d_model))
    y_full, _ = moe.apply_expert_share(full, p, x)
    shared = moe.layers.apply_mlp(
        dataclasses.replace(full, d_ff=full.shared_d_ff), p["shared"], x)
    total = shared
    for j in range(4):
        cfg = dataclasses.replace(full, experts_held=4, expert_offset=4 * j)
        pj = dict(p, **{k: p[k][4 * j:4 * j + 4]
                        for k in ("w_gate", "w_up", "w_down")})
        yj, _ = moe.apply_expert_share(cfg, pj, x)
        total = total + (yj - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_full),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_mla_decode_kernel_matches_oracle(dtype, tol):
    """The Pallas kernel in interpret mode against its jnp oracle: ragged
    lengths (one entry, one block, several blocks, a partial last block),
    page tables that share pages, two layers of one pool."""
    B, H, W, V, ps, n, P, Ly = 4, 4, 128, 96, 4, 8, 24, 2
    k = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(k[0], (B, H, W), jnp.float32).astype(dtype)
    pool = jax.random.normal(k[1], (Ly, P, ps, W), jnp.float32).astype(dtype)
    pages = np.array(jax.random.randint(k[2], (B, n), 1, P), np.int32)
    pages[1, :4] = pages[0, :4]
    pages[3, :2] = pages[2, :2]
    lengths = jnp.array([1, 8, 27, 32], jnp.int32)
    for layer in (0, 1):
        want = ops.mla_decode(q, pool, jnp.int32(layer), pages, lengths,
                              value=V, sm_scale=0.2, impl="ref")
        got = ops._mla_decode_pallas(q, pool, jnp.int32(layer), pages,
                                     lengths, V, 0.2, 2, True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol,
                                   rtol=tol)


def test_yarn_inv_freq_matches_transformers():
    pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS
    cfg = configs.get("deepseek-v2-lite")
    hf = tr.DeepseekV2Config(
        hidden_size=cfg.d_model, num_attention_heads=cfg.num_heads,
        qk_rope_head_dim=cfg.qk_rope_dim, qk_nope_head_dim=cfg.qk_nope_dim,
        rope_theta=cfg.rope_theta, max_position_embeddings=163840,
        rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 0.707,
                      "mscale_all_dim": 0.707,
                      "original_max_position_embeddings": 4096})
    inv, attention_factor = ROPE_INIT_FUNCTIONS["yarn"](hf, "cpu")
    np.testing.assert_allclose(mla.rope_inv_freq(cfg), inv.numpy(),
                               rtol=1e-6)
    np.testing.assert_allclose(
        reference_mla.inv_freq(dataclasses.asdict(cfg)), inv.numpy(),
        rtol=1e-6)
    assert attention_factor == pytest.approx(1.0)


def test_reference_matches_transformers_deepseek_v2():
    """The plain reference against the ``transformers`` port, every expert
    held and YaRN off (where the port's softmax scale, which leaves out
    DeepSeek's mscale², agrees with the published one)."""
    torch = pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")
    cfg = _cfg(num_experts=8, experts_held=8, expert_offset=0,
               experts_per_token=3, rope_scaling_factor=1.0, shared_d_ff=64)
    hf_cfg = tr.DeepseekV2Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
        intermediate_size=cfg.d_ff, moe_intermediate_size=cfg.moe_d_ff,
        num_hidden_layers=cfg.num_layers, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_heads, n_shared_experts=2,
        n_routed_experts=cfg.num_experts, num_experts_per_tok=3,
        routed_scaling_factor=1.0, kv_lora_rank=cfg.kv_lora_rank,
        q_lora_rank=None, qk_rope_head_dim=cfg.qk_rope_dim,
        qk_nope_head_dim=cfg.qk_nope_dim, v_head_dim=cfg.v_head_dim,
        topk_method="greedy", n_group=1, topk_group=1, norm_topk_prob=False,
        first_k_dense_replace=1, rms_norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta, rope_scaling=None,
        tie_word_embeddings=False, attn_implementation="eager")
    assert cfg.shared_d_ff == 2 * cfg.moe_d_ff
    torch.manual_seed(0)
    model = tr.DeepseekV2ForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for name, w in model.named_parameters():
            if w.ndim == 1:
                w.copy_(1 + 0.1 * torch.randn_like(w))
            else:
                w.copy_(torch.randn_like(w) * w.shape[-1] ** -0.5)
    sd = {k: jnp.asarray(v.numpy()) for k, v in model.state_dict().items()}

    def lin(name):
        return {"kernel": sd[name + ".weight"].T}

    def block(i, dense):
        pre = f"model.layers.{i}."
        a = pre + "self_attn."
        out = {"norm": {"scale": sd[pre + "input_layernorm.weight"]},
               "mlp_norm": {"scale": sd[pre + "post_attention_layernorm"
                                        ".weight"]},
               "attn": {"wq": lin(a + "q_proj"),
                        "wkv_a": lin(a + "kv_a_proj_with_mqa"),
                        "kv_norm": {"scale": sd[a + "kv_a_layernorm.weight"]},
                        "wkv_b": lin(a + "kv_b_proj"),
                        "wo": lin(a + "o_proj")}}
        m = pre + "mlp."
        if dense:
            out["mlp"] = {k: lin(m + h) for k, h in
                          (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                           ("w_down", "down_proj"))}
            return out
        ex = [m + f"experts.{j}." for j in range(cfg.num_experts)]
        out["mlp"] = {
            "router": {"kernel": sd[m + "gate.weight"].T},
            "w_gate": jnp.stack([sd[e + "gate_proj.weight"].T for e in ex]),
            "w_up": jnp.stack([sd[e + "up_proj.weight"].T for e in ex]),
            "w_down": jnp.stack([sd[e + "down_proj.weight"].T for e in ex]),
            "shared": {k: lin(m + "shared_experts." + h) for k, h in
                       (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                        ("w_down", "down_proj"))}}
        return out

    params = {"embed": {"tokens": sd["model.embed_tokens.weight"],
                        "head": lin("lm_head")},
              "lead": {"0": block(0, True)},
              "blocks": {"0": jax.tree.map(lambda *a: jnp.stack(a),
                                           block(1, False), block(2, False))},
              "final_norm": {"scale": sd["model.norm.weight"]}}
    seq = np.random.default_rng(2).integers(0, cfg.vocab_size, 24)
    with torch.no_grad():
        want = model(torch.as_tensor(seq[None])).logits[0].numpy()
    got = _ref_logits(cfg, params, seq)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
