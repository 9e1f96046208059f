"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each function mirrors its kernel's exact semantics (masking, ring layout,
accumulation dtypes) with straightforward jnp code. Kernel tests sweep
shapes/dtypes and assert allclose against these.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    window: Optional[int] = None,
                    sm_scale: Optional[float] = None) -> jax.Array:
    """q [B,Sq,H,dh], k/v [B,Sk,KV,dh] (H % KV == 0) -> [B,Sq,H,dh]."""
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    sm_scale = sm_scale if sm_scale is not None else dh ** -0.5
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    logits = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    q_pos = jnp.arange(Sq) + (Sk - Sq)   # right-aligned queries
    k_pos = jnp.arange(Sk)
    diff = q_pos[:, None] - k_pos[None, :]
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok &= diff >= 0
    if window is not None:
        ok &= diff < window
    logits = jnp.where(ok[None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array,
                     sm_scale: Optional[float] = None) -> jax.Array:
    """One-token attention against a cache.

    q [B,H,dh]; k/v [B,L,KV,dh]; valid [B,L] bool -> [B,H,dh].

    A row with no valid slot outputs zeros — the kernel's convention (its
    online-softmax accumulator never runs, so l=0 finalizes to 0), not the
    uniform-softmax mean a plain softmax over all-NEG_INF would give.
    """
    B, H, dh = q.shape
    KV = k.shape[2]
    sm_scale = sm_scale if sm_scale is not None else dh ** -0.5
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    logits = jnp.einsum("bhd,blhd->bhl", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * sm_scale
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(valid.any(axis=-1)[:, None, None], w, 0.0)
    out = jnp.einsum("bhl,blhd->bhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, pages: jax.Array,
                           valid: jax.Array,
                           sm_scale: Optional[float] = None) -> jax.Array:
    """One-token attention against a *paged* cache.

    q [B,H,dh]; k/v pages [P,ps,KV,dh]; pages [B,n] int32 (per-row page
    list); valid [B,n*ps] bool over *logical* slots -> [B,H,dh].

    Semantically: gather each row's pages into its logical [n*ps] cache
    view, then exactly ``decode_attention`` — including the all-invalid ->
    zeros contract. The Pallas kernel walks the page list block-by-block
    instead of materializing the gather.
    """
    B = q.shape[0]
    ps, KV, dh = k_pages.shape[1:]
    n = pages.shape[1]
    k = k_pages[pages].reshape(B, n * ps, KV, dh)
    v = v_pages[pages].reshape(B, n * ps, KV, dh)
    return decode_attention(q, k, v, valid, sm_scale)


def mla_decode(q: jax.Array, pool: jax.Array, layer: jax.Array,
               pages: jax.Array, lengths: jax.Array, *, value: int,
               sm_scale: float) -> jax.Array:
    """Paged latent-attention decode (``kernels.mla_decode``'s contract).

    q [B,H,W]; pool [Lyr,P,ps,W]; pages [B,n]; lengths [B] -> [B,H,value]:
    gather layer ``layer``'s pages of each row into its logical [n*ps, W]
    view, score every head against every entry at a position below the
    row's length, and sum the entries' first ``value`` columns under the
    softmax.
    """
    B = q.shape[0]
    ps, W = pool.shape[2:]
    kv = pool[layer][pages].reshape(B, -1, W).astype(jnp.float32)
    logits = jnp.einsum("bhw,blw->bhl", q.astype(jnp.float32), kv) * sm_scale
    pos = jnp.arange(kv.shape[1], dtype=jnp.int32)
    logits = jnp.where(pos[None, None, :] < lengths[:, None, None], logits,
                       NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhl,blv->bhv", w, kv[..., :value]).astype(q.dtype)


def rglru_scan(a: jax.Array, x: jax.Array, h0: jax.Array) -> tuple:
    """Sequential linear recurrence h_t = a_t h_{t-1} + x_t (all fp32).

    a/x [B,S,W], h0 [B,W] -> (y [B,S,W], h_last [B,W]).
    """
    def step(h, ax):
        at, xt = ax
        h = at * h + xt
        return h, h

    a_t = jnp.moveaxis(a.astype(jnp.float32), 1, 0)
    x_t = jnp.moveaxis(x.astype(jnp.float32), 1, 0)
    h_last, ys = jax.lax.scan(step, h0.astype(jnp.float32), (a_t, x_t))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h_last


def ssm_scan(u: jax.Array, delta: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, h0: jax.Array) -> tuple:
    """Mamba-1 selective scan.

    u/delta [B,S,Di], A [Di,N], B/C [B,S,N], D [Di], h0 [B,Di,N]
    -> (y [B,S,Di], h_last [B,Di,N]).
    """
    uf = u.astype(jnp.float32)
    df = delta.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)

    def step(h, inp):
        ut, dt, Bt, Ct = inp
        dA = jnp.exp(dt[:, :, None] * A[None])          # [b,Di,N]
        dBu = (dt * ut)[:, :, None] * Bt[:, None, :]
        h = dA * h + dBu
        y = jnp.einsum("bdn,bn->bd", h, Ct) + D * ut
        return h, y

    inp = (jnp.moveaxis(uf, 1, 0), jnp.moveaxis(df, 1, 0),
           jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0))
    h_last, ys = jax.lax.scan(step, h0.astype(jnp.float32), inp)
    return jnp.moveaxis(ys, 0, 1).astype(u.dtype), h_last
