"""Mixture-of-experts layers.

``apply_moe`` is Mixtral's training layer: token-choice top-k with dense
(GShard) dispatch. Top-k routing builds dispatch/combine tensors and experts
run as stacked einsums — no gather/scatter, fully shardable. Expert weights
are 2-D sharded ``P(None, 'data', 'model')`` (FSDP × TP); the dispatch
einsums induce the all-to-all-equivalent collectives under SPMD. An
auxiliary load-balancing loss (Switch style) is returned to the trainer. Its
capacity factor drops tokens and couples the rows of a batch.

``apply_expert_share`` is the serving layer (DeepSeek-V2's fine-grained
experts plus shared experts), for a replica that holds only its share of
the experts: it routes over all ``num_experts``, computes only the
``experts_held`` it holds, drops nothing and treats every token alone.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.config import ModelConfig
from repro.sharding import shard


def init_moe(cfg: ModelConfig, key) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    s_in, s_out = d ** -0.5, f ** -0.5
    return {
        "router": layers.init_linear(ks[0], d, e),
        "w_gate": jax.random.normal(ks[1], (e, d, f), jnp.float32) * s_in,
        "w_up": jax.random.normal(ks[2], (e, d, f), jnp.float32) * s_in,
        "w_down": jax.random.normal(ks[3], (e, f, d), jnp.float32) * s_out,
    }


def _topk_mask(gates: jax.Array, k: int) -> jax.Array:
    """[T, E] -> 0/1 mask of the top-k experts per token."""
    _, idx = jax.lax.top_k(gates, k)
    return jax.nn.one_hot(idx, gates.shape[-1], dtype=gates.dtype).sum(-2)


# Tokens are routed within groups of at most this many tokens; capacity is
# per-group, so the dispatch one-hot is [.., g, E, C_g] instead of
# [.., S, E, C_S] — at 32k sequence that's an 8× memory difference.
GROUP_TOKENS = 4096


def apply_moe(cfg: ModelConfig, p: dict, x: jax.Array
              ) -> tuple[jax.Array, jax.Array]:
    """x [B, S, D] -> (out [B, S, D], aux_loss scalar)."""
    B0, S0, D = x.shape
    if S0 > GROUP_TOKENS and S0 % GROUP_TOKENS == 0:
        # GShard grouping: route within fixed-size token groups.
        n = S0 // GROUP_TOKENS
        out, aux = apply_moe(cfg, p,
                             x.reshape(B0 * n, GROUP_TOKENS, D))
        return out.reshape(B0, S0, D), aux

    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    # Capacity per expert (tokens routed above it are dropped — standard).
    C = int(cfg.moe_capacity_factor * K * S / E)
    C = max(C, 1)

    xt = x.reshape(B, S, D)
    logits = layers.apply_linear(p["router"], xt).astype(jnp.float32)  # [B,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    mask = _topk_mask(probs, K)                                # [B,S,E] 0/1
    gates = probs * mask
    # Renormalize the chosen gates (Mixtral renormalizes over top-k).
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)

    # Position of each token within its expert's capacity buffer.
    pos = jnp.cumsum(mask, axis=1) * mask - 1.0                # [B,S,E]
    in_cap = (pos >= 0) & (pos < C)
    gates = jnp.where(in_cap, gates, 0.0)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=x.dtype)  # [B,S,E,C]
    dispatch = pos_oh * in_cap.astype(x.dtype)[..., None]             # [B,S,E,C]
    combine = dispatch * gates.astype(x.dtype)[..., None]             # [B,S,E,C]

    # Dispatch tokens to expert buffers, run experts, combine.
    xe = jnp.einsum("bsec,bsd->becd", dispatch, xt)            # [B,E,C,D]
    xe = shard(xe, "dp", None, None, None)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, p["w_gate"].astype(x.dtype)))
    h = h * jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(x.dtype))
    h = shard(h, "dp", None, None, "tp")
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(x.dtype))
    y = jnp.einsum("bsec,becd->bsd", combine, ye)              # [B,S,D]

    # Switch-style load-balance loss: E * sum_e f_e * P_e.
    f_e = mask.mean(axis=(0, 1))                               # fraction routed
    p_e = probs.mean(axis=(0, 1))                              # mean router prob
    aux = E * jnp.sum(f_e * p_e) * cfg.router_aux_loss
    return y.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# Serving: this replica's share of fine-grained experts + a shared expert
# ---------------------------------------------------------------------------

def init_expert_share(cfg: ModelConfig, key) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.experts_held
    ks = jax.random.split(key, 5)
    s_in, s_out = d ** -0.5, f ** -0.5
    shared = dataclasses.replace(cfg, d_ff=cfg.shared_d_ff, mlp="swiglu",
                                 mlp_bias=False)
    return {
        "router": layers.init_linear(ks[0], d, cfg.num_experts),
        "w_gate": jax.random.normal(ks[1], (e, d, f), jnp.float32) * s_in,
        "w_up": jax.random.normal(ks[2], (e, d, f), jnp.float32) * s_in,
        "w_down": jax.random.normal(ks[3], (e, f, d), jnp.float32) * s_out,
        "shared": layers.init_mlp(shared, ks[4]),
    }


def expert_gates(cfg: ModelConfig, router: dict, x: jax.Array) -> jax.Array:
    """Gates of the held experts [..., experts_held], float32: softmax of
    the router over all ``num_experts``, zero outside each token's top-k
    (DeepSeek-V2's routing: not renormalised, scale 1), then the held
    slice."""
    # Products of compute-dtype values, summed in float32: the published
    # router's float32 logits of bfloat16 activations and weights.
    logits = jnp.dot(x, router["kernel"].astype(x.dtype),
                     preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates = probs * _topk_mask(probs, cfg.experts_per_token)
    return gates[..., cfg.expert_offset:cfg.expert_offset + cfg.experts_held]


def apply_expert_share(cfg: ModelConfig, p: dict, x: jax.Array
                       ) -> tuple[jax.Array, jax.Array]:
    """x [B, S, D] -> (out [B, S, D], routed pairs on held experts [B, S]).

    out = Σ over held experts of gate · SwiGLU_e(x) + SwiGLU_shared(x). Every
    held expert runs on every token (a zero gate contributes zero): at
    decode batch sizes an expert matmul reads its weights once whatever the
    rows, and no token is ever dropped or depends on its neighbours."""
    gates = expert_gates(cfg, p["router"], x)               # [B,S,e]
    dt = x.dtype
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["w_gate"].astype(dt)))
    h = h * jnp.einsum("bsd,edf->bsef", x, p["w_up"].astype(dt))
    h = h * gates.astype(dt)[..., None]
    y = jnp.einsum("bsef,efd->bsd", h, p["w_down"].astype(dt))
    shared = dataclasses.replace(cfg, mlp="swiglu", mlp_bias=False)
    y = y + layers.apply_mlp(shared, p["shared"], x)
    return y, (gates > 0).sum(-1).astype(jnp.int32)
