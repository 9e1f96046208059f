"""Operations and bytes of a latent-attention MoE decoder (DeepSeek-V2) on
this chip's share of the experts, from the model's sizes.

Model FLOPs count two operations per weight that multiplies an activation,
per token, in the published (non-absorbed) form: the attention projections
(``wq``, ``wkv_a``, ``wkv_b``, ``wo``), the dense layers' MLP, each MoE
layer's router and shared expert, and the output head; plus, per routed
token-expert pair on a held expert, that expert's three matrices; plus the
attention products, four per query head per key seen over the score width
(``qk_nope + qk_rope``) and the value width. Routed pairs per token and MoE
layer are taken at their expectation, ``experts_per_token · experts_held /
num_experts`` (``moe_expert_load`` reads the measured number).

Bytes of the MLA decode kernel are the least it must read: each live cache
entry (``kv_lora_rank + qk_rope_dim`` bfloat16 values) once per row, step
and layer. Its operations: per head and entry a score over that width and
a sum over the latent.
"""

from __future__ import annotations

CACHE_BYTES = 2             # bfloat16 cache entries


def _moe_layers(m: dict) -> int:
    return m["num_layers"] - m.get("first_dense", 0)


def dense_matmul_params(m: dict) -> int:
    """Weights every token multiplies, over all layers (held experts
    apart)."""
    d, H, r = m["d_model"], m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    attn = d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d
    lead = m.get("first_dense", 0)
    moe = d * m["num_experts"] + 3 * d * m["shared_d_ff"]
    return (m["num_layers"] * attn + lead * 3 * d * m["d_ff"]
            + _moe_layers(m) * moe + d * m["vocab_size"])


def routed_pairs_per_token(m: dict) -> float:
    """Expected routed pairs on held experts per token and MoE layer."""
    return m["experts_per_token"] * m["experts_held"] / m["num_experts"]


def token_flops(m: dict) -> float:
    """Matrix FLOPs of one token (attention products apart)."""
    routed = 3 * m["d_model"] * m["moe_d_ff"] * routed_pairs_per_token(m)
    return 2.0 * (dense_matmul_params(m) + _moe_layers(m) * routed)


def sequence_flops(m: dict, lo: int, hi: int) -> float:
    """Model FLOPs of the tokens at positions ``lo <= t < hi`` of one
    sequence; the token at ``t`` sees ``t + 1`` keys."""
    if hi <= lo:
        return 0.0
    keys = (hi * (hi + 1) - lo * (lo + 1)) // 2
    per_key = 2 * m["num_heads"] * (m["qk_nope_dim"] + m["qk_rope_dim"]
                                    + m["v_head_dim"])
    return token_flops(m) * (hi - lo) + float(per_key) * m["num_layers"] * keys


def request_flops(m: dict, reused: int, prompt: int, new: int) -> float:
    """Prefill of the prompt past the ``reused`` prefix-cache tokens, then
    the ``new - 1`` decode steps after the first token."""
    return sequence_flops(m, reused, prompt + max(new - 1, 0))


def latent_bytes(m: dict, entries: int) -> float:
    """Least bytes the MLA decode kernel reads for ``entries`` live cache
    entries, summed over rows and steps, in every layer."""
    return float(entries) * m["num_layers"] * (
        m["kv_lora_rank"] + m["qk_rope_dim"]) * CACHE_BYTES


def latent_flops(m: dict, entries: int) -> float:
    """Kernel operations for ``entries`` live cache entries in every layer:
    per head a score over the cache width and a sum over the latent."""
    r, dr = m["kv_lora_rank"], m["qk_rope_dim"]
    return float(entries) * m["num_layers"] * m["num_heads"] * 2 * (
        r + dr + r)
