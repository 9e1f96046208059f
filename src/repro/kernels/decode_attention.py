"""Flash-decode: single-token attention over a long KV cache (Pallas TPU).

The ``decode_32k`` / ``long_500k`` serving shapes are dominated by
streaming the KV cache once per new token; this kernel blocks the cache
HBM->VMEM along L with online-softmax state in VMEM scratch, so HBM
traffic is exactly one pass over K and V (the roofline minimum for
decode). Ring-buffer validity (which slots hold live tokens, window
eviction) arrives as a precomputed ``valid`` mask — the kernel is layout
agnostic. GQA is native via the index_map (h // group).

Block layout (Mosaic tiles the last two dims of every block by (8, 128)
unless a block dim equals the array dim): the mask rides as
``[B, nblk, 1, blk]`` so each block's last two dims ``(1, blk)`` equal
the array's, whatever ``B`` is; K/V blocks are ``(blk, dh)``, so the
flat kernel needs ``blk`` a multiple of 128 (or the whole length) —
``kernels.ops`` pads the length up to that — and the paged kernel's
``(ps, dh)`` page block equals the pool's own trailing dims.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, sm_scale: float):
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    valid = valid_ref[0, 0] != 0                     # [1, bl]

    @pl.when(jnp.any(valid))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [1, dh]
        k = k_ref[0, 0].astype(jnp.float32)          # [bl, dh]
        v = v_ref[0, 0].astype(jnp.float32)          # [bl, dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                              # [1, bl]
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]                           # [1]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(valid, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array, *,
                     sm_scale: Optional[float] = None,
                     block_l: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q [B,H,dh]; k/v [B,L,KV,dh]; valid [B,L] (bool/int) -> [B,H,dh].

    ``L`` must be a multiple of ``block_l``; on TPU ``block_l`` must also
    be a multiple of 128 or equal ``L`` (``kernels.ops`` pads to that).
    """
    B, H, dh = q.shape
    L, KV = k.shape[1], k.shape[2]
    assert H % KV == 0
    group = H // KV
    sm_scale = sm_scale if sm_scale is not None else dh ** -0.5
    block_l = min(block_l, L)
    if L % block_l:
        raise ValueError(f"cache length {L} is not a multiple of the "
                         f"block {block_l}")
    nblk = L // block_l

    qt = q[:, :, None, :]                     # [B, H, 1, dh]
    kt = k.transpose(0, 2, 1, 3)              # [B, KV, L, dh]
    vt = v.transpose(0, 2, 1, 3)
    valid_i = valid.astype(jnp.int32).reshape(B, nblk, 1, block_l)

    grid = (B, H, nblk)
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, dh), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_l, dh),
                         lambda b, h, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_l, dh),
                         lambda b, h, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, 1, block_l), lambda b, h, j: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, dh), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, valid_i)
    return out[:, :, 0, :]


def _paged_kernel(pages_ref, q_ref, k_ref, v_ref, valid_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, sm_scale: float):
    # Same online-softmax body as _decode_kernel; pages_ref is consumed by
    # the index maps (scalar prefetch), not the body.
    del pages_ref
    _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
                   m_scr, l_scr, acc_scr, sm_scale=sm_scale)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, pages: jax.Array,
                           valid: jax.Array, *,
                           sm_scale: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """Flash-decode over a paged KV pool.

    q [B,H,dh]; k/v pages [P,ps,KV,dh]; pages [B,n] int32; valid [B,n*ps]
    over logical slots -> [B,H,dh].

    The natural block is one page: grid step (b, h, j) streams row b's
    j-th *logical* page, and the page list rides in as a scalar-prefetch
    operand so the K/V index maps can point the DMA at the physical page
    ``pages[b, j]`` — the gather never materializes. The ``valid`` mask is
    logical-slot indexed, so its index map stays (b, j). Online-softmax
    state in VMEM scratch, identical to the flat kernel. Which page sizes
    the chip can tile is ``kernels.ops.paged_page_size_ok``'s rule.
    """
    B, H, dh = q.shape
    P, ps, KV = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    n = pages.shape[1]
    assert valid.shape == (B, n * ps), (valid.shape, B, n, ps)
    assert H % KV == 0
    group = H // KV
    sm_scale = sm_scale if sm_scale is not None else dh ** -0.5

    qt = q[:, :, None, :]                       # [B, H, 1, dh]
    kt = k_pages.transpose(2, 0, 1, 3)          # [KV, P, ps, dh]
    vt = v_pages.transpose(2, 0, 1, 3)
    valid_i = valid.astype(jnp.int32).reshape(B, n, 1, ps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, n),
        in_specs=[
            pl.BlockSpec((1, 1, 1, dh), lambda b, h, j, pg: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, dh),
                         lambda b, h, j, pg: (h // group, pg[b, j], 0, 0)),
            pl.BlockSpec((1, 1, ps, dh),
                         lambda b, h, j, pg: (h // group, pg[b, j], 0, 0)),
            pl.BlockSpec((1, 1, 1, ps), lambda b, h, j, pg: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, dh),
                               lambda b, h, j, pg: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, dh), q.dtype),
        interpret=interpret,
    )(pages.astype(jnp.int32), qt, kt, vt, valid_i)
    return out[:, :, 0, :]
