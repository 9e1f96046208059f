"""Paged latent-attention decode (Pallas TPU), DeepSeek-V2's absorbed form.

In decode, multi-head latent attention is multi-query attention over one
shared "KV head": every query head scores ``[q_lat; q_pe]`` against the
cached ``[c; k_pe]`` entry (``latent`` wide, 576 for DeepSeek-V2) and sums
the ``c`` part (the first ``value`` columns) under the softmax. So the
kernel reads each live cache entry once per row and step, for all heads
together.

The cache is the engine's page pool with a leading layer axis, ``[Lyr, P,
ps, latent]``, left in HBM (``pl.ANY``) and read in place: the page table
and the row lengths ride in by scalar prefetch, and each block of
``pages_per_block`` pages is gathered into VMEM by one DMA per page,
double-buffered, the next block (or the next row's first) started before
the current one is waited on. A row reads ``ceil(length / block)`` blocks
and no more, so short rows cost little. Online softmax in float32 VMEM
scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _kernel(lengths_ref, pages_ref, layer_ref, q_ref, pool_hbm, o_ref,
            buf, sems, slot_ref, m_scr, l_scr, acc_scr, *,
            ppb: int, n: int, value: int, sm_scale: float):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    ps = buf.shape[2]
    bk = ppb * ps
    layer = layer_ref[0]

    def copies(row, blk, slot):
        base = row * n + blk * ppb
        return [pltpu.make_async_copy(
            pool_hbm.at[layer, pages_ref[base + i]], buf.at[slot, i],
            sems.at[slot]) for i in range(ppb)]

    def start(row, blk, slot):
        for c in copies(row, blk, slot):
            c.start()

    length = lengths_ref[b]
    nblk = (length + bk - 1) // bk

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        start(0, 0, 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q = q_ref[0]                                          # [H, latent]

    def body(j, carry):
        slot = slot_ref[0]
        last = j + 1 >= nblk

        @pl.when(jnp.logical_not(last))
        def _next_block():
            start(b, j + 1, 1 - slot)

        @pl.when(jnp.logical_and(last, b + 1 < nb))
        def _next_row():
            start(b + 1, 0, 1 - slot)

        for c in copies(b, j, slot):
            c.wait()
        kv = buf[slot].reshape(bk, buf.shape[3])          # [bk, latent]
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                  # [H, bk]
        pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[...]                               # [H, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :value], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        slot_ref[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, nblk, body, 0)
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                ).astype(o_ref.dtype)


def mla_decode(q: jax.Array, pool: jax.Array, layer: jax.Array,
               pages: jax.Array, lengths: jax.Array, *, value: int,
               sm_scale: float, pages_per_block: int = 16,
               interpret: bool = False) -> jax.Array:
    """q [B,H,latent]; pool [Lyr,P,ps,latent]; layer int32 scalar; pages
    [B,n] int32; lengths [B] int32 (each >= 1: the keys at positions
    ``< length`` count) -> [B,H,value], the softmax-weighted sum of each
    entry's first ``value`` columns.

    ``n`` must be a multiple of ``pages_per_block`` (``kernels.ops`` pads
    the table with the trash page 0, which no length reaches).
    """
    B, H, W = q.shape
    ps = pool.shape[2]
    n = pages.shape[1]
    if n % pages_per_block:
        raise ValueError(f"page table width {n} is not a multiple of "
                         f"{pages_per_block} pages")
    kernel = functools.partial(_kernel, ppb=pages_per_block, n=n,
                               value=value, sm_scale=sm_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, value), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages_per_block, ps, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, value), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value), q.dtype),
        # Rows run in order: each prefetches the next row's first block.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), pages.reshape(-1).astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, pool)
