"""Collective matmul: overlap TP all-gather with compute (shard_map).

The XLA-inserted all-gather for a column-parallel matmul serializes
communication before compute. The ring formulation below (Wang et al.,
"Overlap communication with dependent computation", the standard TPU
collective-matmul) decomposes

    Y = X @ W,   X sharded over the TP axis on its contraction dim

into TP steps: each step matmuls the locally-held X shard against the
matching W rows while ``ppermute`` ships the next X shard around the ring
— communication rides the ICI while the MXU stays busy. On TPU the XLA
scheduler overlaps the ppermute send/recv of step i+1 with the dot of
step i (async collective-permute); wall-clock ≈ max(compute, comm) instead
of compute + comm.

Used as an opt-in replacement for the first FFN matmul (hillclimb lever);
validated against the plain einsum in tests on a host-device mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _ring_matmul(axis_name: str, tp: int, x_shard: jax.Array, w: jax.Array):
    """Inside shard_map. x_shard: [B, S, D/tp]; w: [D/tp·tp?, F/tp] — w holds
    this device's column shard with FULL D rows: [D, F/tp].

    Each step contributes x_shard_j @ w[rows_j] and rotates x. ``tp`` is the
    static tp-axis size, taken from the mesh by the caller (the ppermute
    permutation and loop trip count must be static; ``jax.lax.axis_size``
    does not exist on older jax).
    """
    idx = jax.lax.axis_index(axis_name)
    d_shard = x_shard.shape[-1]

    def rows(j):
        # Which D-rows of w the shard arriving at step s came from.
        return jax.lax.dynamic_slice_in_dim(w, j * d_shard, d_shard, axis=0)

    def body(s, carry):
        acc, x_cur = carry
        src = jnp.mod(idx + s, tp)          # owner of the shard we now hold
        acc = acc + jnp.einsum("bsd,df->bsf", x_cur,
                               rows(src).astype(x_cur.dtype))
        x_nxt = jax.lax.ppermute(
            x_cur, axis_name,
            [(i, (i - 1) % tp) for i in range(tp)])
        return acc, x_nxt

    acc = jnp.zeros(x_shard.shape[:-1] + (w.shape[-1],),
                    jnp.promote_types(x_shard.dtype, jnp.bfloat16))
    acc, _ = jax.lax.fori_loop(0, tp, body, (acc, x_shard))
    return acc.astype(x_shard.dtype)


def collective_matmul(x: jax.Array, w: jax.Array, mesh: Mesh,
                      tp_axis: str = "model",
                      dp_axes=("data",)) -> jax.Array:
    """Y[B,S,F] = X[B,S,D] @ W[D,F], X feature-sharded over ``tp_axis``,
    W column-sharded — without a blocking X all-gather."""
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    fn = jax.shard_map(
        functools.partial(_ring_matmul, tp_axis, mesh.shape[tp_axis]),
        mesh=mesh,
        in_specs=(P(dp_spec, None, tp_axis), P(None, tp_axis)),
        out_specs=P(dp_spec, None, tp_axis),
        check_vma=False,  # fori_loop carry mixes varying/unvarying axes
    )
    return fn(x, w)
