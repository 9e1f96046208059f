"""Plain float32 reference of a latent-attention MoE decoder (DeepSeek-V2,
arXiv:2405.04434), on this chip's share of the experts.

Follows the published layer equations in their published (non-absorbed)
form, written from the configuration's sizes and nothing of the program.
Per layer: RMSNorm; ``q = h·W_q`` split per head into ``q_nope`` and
``q_pe``; ``[c; k_pe] = h·W_kva``; ``c`` RMS-normed by ``kv_norm``;
``[k_nope; v] = c·W_kvb`` per head; keys ``[k_nope; rope(k_pe)]`` (one
``k_pe`` for every head), queries ``[q_nope; rope(q_pe)]``; causal softmax
attention with scale ``(qk_nope + qk_rope)^-0.5 · m²``, ``m = 0.1 ·
mscale_all_dim · ln(factor) + 1``; the output projection. RoPE rotates
interleaved pairs with YaRN's inverse frequencies (DeepSeek's correction
range and linear ramp) and a cos/sin multiplier of ``m(mscale) /
m(mscale_all_dim)``. Then RMSNorm and the MLP: the leading
``first_dense`` layers a SwiGLU of width ``d_ff``; the others the sum over
this chip's held experts of ``p_e · SwiGLU_e(h)``, where ``p`` is the
softmax of the float32 router over all ``num_experts``, zero outside each
token's top ``experts_per_token`` (not renormalised, scale 1, as
DeepSeek-V2 routes), plus the shared SwiGLU of width
``shared_d_ff``. A final RMSNorm and the untied head. Every matrix product
runs at ``Precision.HIGHEST`` in float32, with no cache, kernel or
batching; attention runs in blocks of ``Q_BLOCK`` queries so that 17k
positions fit.

``quant=True`` is the control: the same equations with every projection's
weights and inputs rounded to float8 (e4m3, scaled per output channel and
per token; experts too), the next precision below the bfloat16 the
configuration computes in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
Q_BLOCK = 512           # query rows per attention block


def _f32(a):
    return a.astype(jnp.float32)


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x [..., in] · w [in, out]."""
    w = _f32(w)
    if quant:
        x, w = _fp8(x, -1), _fp8(w, -2)
    return jnp.dot(x, w, precision=HI)


def _rms(m, scale, x):
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True)
                          + m.get("norm_eps", 1e-6))
    return x * _f32(scale)


def _yarn_m(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(m) -> np.ndarray:
    """RoPE inverse frequencies of the ``qk_rope_dim`` dimensions."""
    dim, base = m["qk_rope_dim"], m.get("rope_theta", 10000.0)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    factor = m.get("rope_scaling_factor", 1.0)
    if factor <= 1:
        return extra
    orig = m["rope_original_len"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(corr(m.get("rope_beta_fast", 32.0))), 0)
    hi = min(math.ceil(corr(m.get("rope_beta_slow", 1.0))), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def _rope(m, x, pos):
    """Interleaved pairs of x [S, ..., dr] rotated to positions pos [S]."""
    f = m.get("rope_scaling_factor", 1.0)
    mult = _yarn_m(f, m.get("rope_mscale", 1.0)) / _yarn_m(
        f, m.get("rope_mscale_all_dim") or m.get("rope_mscale", 1.0))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv_freq(m),
                                                         jnp.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + ang.shape[1:])
    sin, cos = jnp.sin(ang) * mult, jnp.cos(ang) * mult
    xp = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = xp[..., 0], xp[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1
                     ).reshape(x.shape)


def softmax_scale(m) -> float:
    s = (m["qk_nope_dim"] + m["qk_rope_dim"]) ** -0.5
    f = m.get("rope_scaling_factor", 1.0)
    if f > 1 and m.get("rope_mscale_all_dim"):
        s *= _yarn_m(f, m["rope_mscale_all_dim"]) ** 2
    return s


def _attention(m, q, k, v):
    """q/k [S,H,dq], v [S,H,dv] -> [S,H,dv]; query blocks bound memory."""
    S = q.shape[0]
    kpos = jnp.arange(S)
    scale = softmax_scale(m)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        s = jnp.where((qpos[:, None] >= kpos[None, :])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    return jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(
        S, q.shape[1], v.shape[-1])


def _swiglu(p, h, quant):
    u = jax.nn.silu(_mm(h, p["w_gate"]["kernel"], quant)) \
        * _mm(h, p["w_up"]["kernel"], quant)
    return _mm(u, p["w_down"]["kernel"], quant)


def _experts(m, p, h, quant):
    """Held experts' gated sum plus the shared expert."""
    probs = jax.nn.softmax(jnp.dot(h, _f32(p["router"]["kernel"]),
                                   precision=HI), axis=-1)
    kth = jax.lax.top_k(probs, m["experts_per_token"])[0][:, -1:]
    gates = jnp.where(probs >= kth, probs, 0.0)
    off = m.get("expert_offset", 0)
    gates = gates[:, off:off + m["experts_held"]]

    def one(y, e):
        wg, wu, wd, g = e
        u = jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant)
        return y + g[:, None] * _mm(u, wd, quant), None

    y, _ = jax.lax.scan(one, _swiglu(p["shared"], h, quant),
                        (p["w_gate"], p["w_up"], p["w_down"], gates.T))
    return y


def _layer(m, quant, dense, x, p):
    S = x.shape[0]
    H, r = m["num_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    pos = jnp.arange(S)
    a = p["attn"]
    h = _rms(m, p["norm"]["scale"], x)
    q = _mm(h, a["wq"]["kernel"], quant).reshape(S, H, dn + dr)
    kva = _mm(h, a["wkv_a"]["kernel"], quant)
    c = _rms(m, a["kv_norm"]["scale"], kva[:, :r])
    k_pe = _rope(m, kva[:, r:], pos)
    kv = _mm(c, a["wkv_b"]["kernel"], quant).reshape(S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe[:, None], (S, H, dr))], -1)
    q = jnp.concatenate([q[..., :dn], _rope(m, q[..., dn:], pos)], -1)
    o = _attention(m, q, k, kv[..., dn:]).reshape(S, H * dv)
    x = x + _mm(o, a["wo"]["kernel"], quant)
    h = _rms(m, p["mlp_norm"]["scale"], x)
    y = _swiglu(p["mlp"], h, quant) if dense else _experts(m, p["mlp"], h,
                                                           quant)
    return x + y, None


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits_at(mkey, params, tokens, idx, quant):
    m = dict(mkey)
    x = _f32(params["embed"]["tokens"])[tokens]
    for i in range(m.get("first_dense", 0)):
        x, _ = _layer(m, quant, True, x, params["lead"][str(i)])
    x, _ = jax.lax.scan(functools.partial(_layer, m, quant, False), x,
                        params["blocks"]["0"])
    h = _rms(m, params["final_norm"]["scale"], x[idx])
    return _mm(h, params["embed"]["head"]["kernel"], quant)


def logits_at(m: dict, params, tokens, idx, quant: bool = False):
    """Logits [len(idx), vocab] at positions ``idx`` of the sequence
    ``tokens`` ([S], S a multiple of ``Q_BLOCK``; pad at the end — a
    causal model's earlier positions never see the padding)."""
    mkey = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))
    return _logits_at(mkey, params, tokens, idx, quant)
