"""Plain float32 reference of a dense decoder (Qwen2, StarCoder2).

Follows the published layer equations, written from the configuration's
sizes and nothing of the program: token embedding; per layer a pre-norm
(RMSNorm, or LayerNorm with bias), q/k/v projections (with bias where the
configuration says), rotary embedding (rotate-half), causal attention over
all earlier positions or the last ``window`` of them (a key at distance
``< window`` is seen), grouped query heads, the output projection, a second
pre-norm and the MLP (SwiGLU, or GELU-tanh with bias); a final norm and the
tied or separate output head. Every matrix product runs at
``Precision.HIGHEST`` in float32, with no cache, kernel or batching.

One departure from StarCoder2's published block: its attention output
projection has a bias, which the served model's weights do not hold, so
the reference has none either.

``quant=True`` is the control: the same equations with every projection's
weights and inputs rounded to float8 (e4m3, scaled per output channel and
per token), the next precision below the bfloat16 the configuration
computes in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
Q_BLOCK = 512           # query rows per attention block


def _f32(a):
    return a.astype(jnp.float32)


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(p, x, quant):
    w = _f32(p["kernel"])
    if quant:
        x, w = _fp8(x, -1), _fp8(w, 0)
    y = jnp.dot(x, w, precision=HI)
    if "bias" in p:
        y = y + _f32(p["bias"])
    return y


def _norm(m, p, x):
    eps = m.get("norm_eps", 1e-6)
    if m.get("norm", "rmsnorm") == "layernorm":
        x = x - x.mean(-1, keepdims=True)
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
    x = x * _f32(p["scale"])
    if "bias" in p:
        x = x + _f32(p["bias"])
    return x


def _rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * inv           # [S, dh/2]
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(m, q, k, v):
    """q [S,H,dh], k/v [S,KV,dh] -> [S,H,dh]; query blocks bound memory."""
    S, H, dh = q.shape
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    window = m.get("window") if m.get("pattern", ["attn"])[0] == "swa" \
        else None
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * dh ** -0.5
        diff = qpos[:, None] - kpos[None, :]
        ok = diff >= 0
        if window is not None:
            ok &= diff < window
        s = jnp.where(ok[None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
    return out.reshape(S, H, dh)


def _layer(m, quant, x, p):
    S = x.shape[0]
    H, KV = m["num_heads"], m["num_kv_heads"]
    dh = m.get("head_dim") or m["d_model"] // H
    pos = jnp.arange(S)
    h = _norm(m, p["norm"], x)
    a = p["attn"]
    q = _linear(a["wq"], h, quant).reshape(S, H, dh)
    k = _linear(a["wk"], h, quant).reshape(S, KV, dh)
    v = _linear(a["wv"], h, quant).reshape(S, KV, dh)
    theta = m.get("rope_theta", 10000.0)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    o = _attention(m, q, k, v).reshape(S, H * dh)
    x = x + _linear(a["wo"], o, quant)
    h = _norm(m, p["mlp_norm"], x)
    mp = p["mlp"]
    if m.get("mlp", "swiglu") == "swiglu":
        u = jax.nn.silu(_linear(mp["w_gate"], h, quant)) \
            * _linear(mp["w_up"], h, quant)
    else:
        u = jax.nn.gelu(_linear(mp["w_up"], h, quant), approximate=True)
    return x + _linear(mp["w_down"], u, quant), None


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits_at(mkey, params, tokens, idx, quant):
    m = dict(mkey)
    x = _f32(params["embed"]["tokens"])[tokens]
    x, _ = jax.lax.scan(functools.partial(_layer, m, quant), x,
                        params["blocks"]["0"])
    h = _norm(m, params["final_norm"], x[idx])
    if m.get("tie_embeddings", False):
        w = _f32(params["embed"]["tokens"]).T
        if quant:
            h, w = _fp8(h, -1), _fp8(w, 0)
        return jnp.dot(h, w, precision=HI)
    return _linear(params["embed"]["head"], h, quant)


def logits_at(m: dict, params, tokens, idx, quant: bool = False):
    """Logits [len(idx), vocab] at positions ``idx`` of the sequence
    ``tokens`` ([S], S a multiple of ``Q_BLOCK``; pad at the end — a
    causal model's earlier positions never see the padding)."""
    mkey = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))
    return _logits_at(mkey, params, tokens, idx, quant)
