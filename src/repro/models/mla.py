"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434).

Per token, ``wkv_a`` makes a latent ``c`` (``kv_lora_rank`` wide, RMS-normed
by ``kv_norm``) and one RoPE key ``k_pe`` shared by every head; the cache
holds ``[c; rope(k_pe)]`` and nothing else. Queries come from ``wq``, split
per head into ``q_nope`` and ``q_pe``.

* Prefill and chunked prefill use the published form: ``wkv_b`` expands the
  latent into per-head ``k_nope`` and ``v``, and the keys are ``[k_nope;
  rope(k_pe)]``.
* Decode uses the absorbed form: ``q_lat = q_nope · W_UKᵀ`` per head, the
  score is ``q_lat·c + rope(q_pe)·rope(k_pe)``, the softmax sums the
  latents, and ``W_UV`` maps the result to each head's value width. This is
  multi-query attention over one wide "KV head", which
  ``kernels.mla_decode`` runs over the page pool in place.

The softmax scale is ``(qk_nope + qk_rope)^-0.5`` times DeepSeek's YaRN
``mscale²`` (``ModelConfig.mla_softmax_scale``). RoPE rotates interleaved
pairs, as DeepSeek's weights are laid out, with YaRN's inverse frequencies
(the correction range and linear ramp of its
``DeepseekV2YarnRotaryEmbedding``).

A cache entry is ``cache_width`` wide: ``kv_lora_rank + qk_rope_dim``
rounded up to the TPU's 128 lanes with zeros (576 -> 640). The chip lays
any minor dimension out in whole lanes, so the pad costs no memory that a
576-wide array would not; spelled out, it lets the kernel's DMAs copy whole
aligned rows.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers
from repro.models.config import ModelConfig

NEG_INF = -2.0 ** 30
LANE = 128
Q_BLOCK = 512           # query rows per attention block in (chunked) prefill


def cache_width(cfg: ModelConfig) -> int:
    w = cfg.kv_lora_rank + cfg.qk_rope_dim
    return -(-w // LANE) * LANE


def init_mla(cfg: ModelConfig, key) -> dict:
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": layers.init_linear(ks[0], d, H * (dn + dr)),
        "wkv_a": layers.init_linear(ks[1], d, r + dr),
        "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
        "wkv_b": layers.init_linear(ks[2], r, H * (dn + dv)),
        "wo": layers.init_linear(ks[3], H * dv, d, scale=(H * dv) ** -0.5),
    }


# ---------------------------------------------------------------------------
# RoPE (YaRN, interleaved pairs)
# ---------------------------------------------------------------------------

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """Inverse frequencies of the ``qk_rope_dim`` RoPE dimensions, with
    YaRN's interpolation when ``rope_scaling_factor`` > 1."""
    dim, base = cfg.qk_rope_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = cfg.rope_scaling_factor
    if factor <= 1:
        return extra.astype(np.float32)
    inter = extra / factor

    def corr_dim(rot):
        return (dim * math.log(cfg.rope_original_len / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp                     # 1: extrapolate, 0: interpolate
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def rope(cfg: ModelConfig, x: jax.Array, pos: jax.Array) -> jax.Array:
    """Rotate interleaved pairs of the last dim of ``x`` [B,S,(H,)dr] at
    positions ``pos`` [B,S]."""
    inv = jnp.asarray(rope_inv_freq(cfg))
    mult = (_yarn_mscale(cfg.rope_scaling_factor, cfg.rope_mscale)
            / _yarn_mscale(cfg.rope_scaling_factor,
                           cfg.rope_mscale_all_dim or cfg.rope_mscale))
    ang = pos[..., None].astype(jnp.float32) * inv        # [B,S,dr/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    sin, cos = jnp.sin(ang) * mult, jnp.cos(ang) * mult
    xp = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    x0, x1 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------

def _project(cfg: ModelConfig, p: dict, x: jax.Array, pos: jax.Array):
    """x [B,S,D] -> (q_nope [B,S,H,dn], rope(q_pe) [B,S,H,dr], cache
    entries [B,S,cache_width])."""
    B, S = x.shape[:2]
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = layers.apply_linear(p["wq"], x).reshape(B, S, H, dn + dr)
    kva = layers.apply_linear(p["wkv_a"], x)
    c = kva[..., :r].astype(jnp.float32)
    c = c * jax.lax.rsqrt((c * c).mean(-1, keepdims=True) + cfg.norm_eps)
    c = (c * p["kv_norm"]["scale"]).astype(x.dtype)
    k_pe = rope(cfg, kva[..., r:], pos)
    pad = jnp.zeros((B, S, cache_width(cfg) - r - dr), x.dtype)
    entry = jnp.concatenate([c, k_pe, pad], -1)
    return q[..., :dn], rope(cfg, q[..., dn:], pos), entry


def _expand(cfg: ModelConfig, p: dict, entries: jax.Array, dtype):
    """Published form: cache entries [B,S,W] -> keys [B,S,H,dn+dr] and
    values [B,S,H,dv]."""
    B, S = entries.shape[:2]
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    entries = entries.astype(dtype)
    kv = layers.apply_linear(p["wkv_b"], entries[..., :r])
    kv = kv.reshape(B, S, H, dn + cfg.v_head_dim)
    k_pe = jnp.broadcast_to(entries[:, :, None, r:r + dr], (B, S, H, dr))
    return jnp.concatenate([kv[..., :dn], k_pe], -1), kv[..., dn:]


def _attend(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
            q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    """Causal attention, q [B,Sq,H,dq] at ``q_pos`` [Sq] against k/v at
    ``k_pos`` [Sk], in blocks of ``Q_BLOCK`` queries (one block's logits
    live at a time) -> [B,Sq,H,dv]."""
    scale = cfg.mla_softmax_scale

    def block(qb, pb):
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(pb[:, None] >= k_pos[None, :], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    Sq = q.shape[1]
    if Sq <= Q_BLOCK or Sq % Q_BLOCK:
        return block(q, q_pos)
    n = Sq // Q_BLOCK
    qs = q.reshape(q.shape[0], n, Q_BLOCK, *q.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(lambda a: block(*a), (qs, q_pos.reshape(n, Q_BLOCK)))
    return out.swapaxes(0, 1).reshape(q.shape[:3] + (v.shape[-1],))


def _out(cfg: ModelConfig, p: dict, o: jax.Array) -> jax.Array:
    B, S = o.shape[:2]
    return layers.apply_linear(p["wo"], o.reshape(B, S, -1))


# ---------------------------------------------------------------------------
# Prefill and chunked prefill (published form)
# ---------------------------------------------------------------------------

def self_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                   positions: jax.Array, return_cache: bool = False):
    """Full-sequence causal attention; with ``return_cache`` also the cache
    entries [B,S,W]."""
    q_nope, q_pe, entries = _project(cfg, p, x, positions)
    k, v = _expand(cfg, p, entries, x.dtype)
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    o = _attend(cfg, jnp.concatenate([q_nope, q_pe], -1), k, v, pos, pos)
    out = _out(cfg, p, o)
    return (out, entries) if return_cache else out


def cache_spec(cfg: ModelConfig, batch: int, length: int, dtype) -> dict:
    """Flat per-row cache, or (``batch`` = pages, ``length`` = page size)
    one layer's page pool."""
    return {"ckv": jax.ShapeDtypeStruct((batch, length, cache_width(cfg)),
                                        dtype)}


def build_cache(entries: jax.Array, context_len: int, dtype) -> dict:
    B, S, W = entries.shape
    c = jnp.zeros((B, context_len, W), dtype)
    return {"ckv": c.at[:, :S].set(entries.astype(dtype))}


def extend_attention(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
                     t0: jax.Array) -> tuple[jax.Array, dict]:
    """Chunked prefill: ``C`` tokens at positions ``t0 ..`` against a flat
    cache [B,L,W] (scalar ``t0``). The chunk's entries are written first —
    the latent cache is no ring (positions stay below L) — and the chunk
    attends over the whole expanded cache under the causal mask."""
    C = x.shape[1]
    pos = jnp.asarray(t0, jnp.int32) + jnp.arange(C, dtype=jnp.int32)
    q_nope, q_pe, entries = _project(cfg, p, x, jnp.broadcast_to(
        pos[None], x.shape[:2]))
    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache["ckv"], entries.astype(cache["ckv"].dtype), pos[0], axis=1)
    k, v = _expand(cfg, p, ckv, x.dtype)
    k_pos = jnp.arange(ckv.shape[1], dtype=jnp.int32)
    o = _attend(cfg, jnp.concatenate([q_nope, q_pe], -1), k, v, pos, k_pos)
    return _out(cfg, p, o), {"ckv": ckv}


# ---------------------------------------------------------------------------
# Decode (absorbed form)
# ---------------------------------------------------------------------------

def _absorbed_query(cfg: ModelConfig, p: dict, q_nope, q_pe) -> jax.Array:
    """[q_nope·W_UKᵀ; rope(q_pe); 0] per head: [B,H,W]."""
    H, r, dn = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_dim
    w = p["wkv_b"]["kernel"].astype(q_nope.dtype)
    w_uk = w.reshape(r, H, dn + cfg.v_head_dim)[..., :dn]
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_uk)
    pad = jnp.zeros(q_lat.shape[:2] + (cache_width(cfg) - r - cfg.qk_rope_dim,),
                    q_lat.dtype)
    return jnp.concatenate([q_lat, q_pe[:, 0], pad], -1)


def _value_out(cfg: ModelConfig, p: dict, o_lat: jax.Array) -> jax.Array:
    """Latent attention output [B,H,r] -> o_proj of W_UV per head [B,1,D]."""
    H, r, dn = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_dim
    w = p["wkv_b"]["kernel"].astype(o_lat.dtype)
    w_uv = w.reshape(r, H, dn + cfg.v_head_dim)[..., dn:]
    o = jnp.einsum("bhc,chv->bhv", o_lat, w_uv)
    return _out(cfg, p, o[:, None])


def decode_attention(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict,
                     t: jax.Array) -> tuple[jax.Array, dict]:
    """One token per row against a flat cache [B,L,W]; ``t`` scalar or
    [B]."""
    from repro.kernels import ref
    B = x.shape[0]
    tb = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
    q_nope, q_pe, entry = _project(cfg, p, x, tb[:, None])
    ckv = cache["ckv"].at[jnp.arange(B), tb].set(
        entry[:, 0].astype(cache["ckv"].dtype))
    q = _absorbed_query(cfg, p, q_nope, q_pe)
    pages = jnp.arange(B, dtype=jnp.int32)[:, None]
    o_lat = ref.mla_decode(q, ckv[None, :, :, :].astype(q.dtype),
                           jnp.int32(0), pages, tb + 1,
                           value=cfg.kv_lora_rank,
                           sm_scale=cfg.mla_softmax_scale)
    return _value_out(cfg, p, o_lat), {"ckv": ckv}


def paged_decode_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                           pool: jax.Array, layer, t: jax.Array,
                           pages: jax.Array, impl: str = "auto"):
    """One token per row against the page pool, read and written in
    place. ``pool`` [Lyr,P,ps,W] holds every layer of a stacked group
    (``layer`` picks one, traced); ``pages`` [B,n] the rows' page tables;
    ``t`` [B] or scalar. The new entry lands at ``pages[b, t // ps]``,
    offset ``t % ps`` (free rows write the trash page), then the kernel
    attends over positions ``<= t``. Returns (out [B,1,D], pool)."""
    from repro.kernels import ops as kernel_ops
    B = x.shape[0]
    ps = pool.shape[2]
    tb = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
    q_nope, q_pe, entry = _project(cfg, p, x, tb[:, None])
    pid = jnp.take_along_axis(pages, (tb // ps)[:, None], axis=1)[:, 0]
    pool = pool.at[layer, pid, tb % ps].set(entry[:, 0].astype(pool.dtype))
    q = _absorbed_query(cfg, p, q_nope, q_pe)
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "dense"
    o_lat = kernel_ops.mla_decode(
        q, pool, jnp.asarray(layer, jnp.int32), pages, tb + 1,
        value=cfg.kv_lora_rank, sm_scale=cfg.mla_softmax_scale,
        impl="pallas" if impl == "flash" else "ref")
    return _value_out(cfg, p, o_lat), pool
