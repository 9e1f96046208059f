"""Gradient compression for the cross-pod (DCN) reduction.

Within a pod, gradient reductions ride ICI and stay uncompressed (XLA
collectives). *Across* pods the all-reduce crosses the data-center network
— the slow, contended link — so we expose an explicit compressed cross-pod
reduction:

  * bf16 reduction: cast-reduce-cast, 2× wire savings, error ≤ 2^-8 rel.
  * int8 + error feedback: per-tensor scale, 4× savings; the quantization
    residual is fed back into the next step's gradient (Seide et al.'s
    1-bit-SGD trick generalized), so the bias does not accumulate.

Implemented as a pure function over (grads, error_state) + a psum inside
``shard_map`` over the 'pod' axis; with one pod it degenerates to a no-op
so the same train step runs everywhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _quantize_int8(x: jax.Array):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jax.Array, scale: jax.Array):
    return q.astype(jnp.float32) * scale


def compress_reduce_pod(grads, error_state, mesh: Mesh,
                        method: str = "int8_ef", pod_axis: str = "pod"):
    """All-reduce ``grads`` over the pod axis with compression.

    grads: pytree of per-pod-averaged fp32 gradients (already reduced
    within the pod by XLA). error_state: pytree like grads (int8_ef) or
    None (bf16). Returns (reduced_grads, new_error_state).
    """
    if pod_axis not in mesh.axis_names or mesh.shape[pod_axis] == 1:
        return grads, error_state

    npod = mesh.shape[pod_axis]

    def _one(g, e):
        def inner(g_shard, e_shard):
            if method == "bf16":
                r = jax.lax.psum(g_shard.astype(jnp.bfloat16), pod_axis)
                return r.astype(jnp.float32) / npod, e_shard
            # int8 with error feedback
            corrected = g_shard + e_shard
            q, scale = _quantize_int8(corrected)
            deq = _dequantize(q, scale)
            new_err = corrected - deq          # what compression dropped
            # int8 psum overflows; sum dequantized fp32 (wire cost is the
            # int8 payload + one scalar — modeled in the roofline).
            r = jax.lax.psum(deq, pod_axis) / npod
            return r, new_err

        spec = P()  # per-pod replicated view of the (already FSDP'd) grad
        fn = jax.shard_map(inner, mesh=mesh,
                           in_specs=(spec, spec), out_specs=(spec, spec),
                           check_vma=False)
        return fn(g, e)

    if error_state is None:
        error_state = jax.tree.map(jnp.zeros_like, grads)
    out = jax.tree.map(_one, grads, error_state)
    reduced = jax.tree.map(lambda t: t[0], out,
                           is_leaf=lambda t: isinstance(t, tuple))
    new_err = jax.tree.map(lambda t: t[1], out,
                           is_leaf=lambda t: isinstance(t, tuple))
    return reduced, new_err


def wire_bytes_saved(grads, method: str = "int8_ef") -> float:
    """Analytic DCN savings vs fp32 ring all-reduce (for §Perf records)."""
    total = sum(x.size * 4 for x in jax.tree.leaves(grads))
    factor = {"bf16": 2.0, "int8_ef": 4.0}[method]
    return total * (1 - 1 / factor)


# -- host/wire-side compression (actor-learner fabric) -----------------------
# The mesh path above compresses inside a shard_map psum. The training
# fabric's chief-driven aggregation instead ships per-learner gradients over
# courier RPC, so compression happens host-side on numpy trees: each learner
# quantizes its contribution with its *own* error-feedback residual, the
# chief dequantizes and averages. The residual is real training state — the
# chief's copy rides in published checkpoints and is resharded on elastic
# restores (see ckpt/elastic.py).

def select_strategy(tree, threshold_bytes: int = 1 << 22) -> str:
    """Pick the wire strategy by gradient size: below the threshold the
    dense fp32 payload is effectively free on a same-host courier, above it
    int8+EF buys 4x on the slow link."""
    total = sum(int(np.asarray(jax.device_get(x)).nbytes)
                for x in jax.tree.leaves(tree))
    return "int8_ef" if total >= threshold_bytes else "dense"


def grad_bytes(tree) -> int:
    return sum(int(np.asarray(jax.device_get(x)).nbytes)
               for x in jax.tree.leaves(tree))


def compress_tree(grads, error_state=None, method: str = "int8_ef"):
    """Compress a gradient pytree into a picklable wire payload.

    Returns ``(payload, new_error_state)``. ``method="dense"`` passes fp32
    through untouched (error_state is returned as-is); ``"int8_ef"`` applies
    per-tensor int8 quantization with error feedback, so the residual of
    what compression dropped is added back into the next step's gradient.
    """
    host = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x), dtype=np.float32), grads)
    if method == "dense":
        return {"method": "dense", "tree": host}, error_state
    if method != "int8_ef":
        raise ValueError(f"unknown wire compression method {method!r}")
    if error_state is None:
        error_state = jax.tree.map(np.zeros_like, host)

    def _one(g, e):
        corrected = g + np.asarray(jax.device_get(e), dtype=np.float32)
        scale = np.float32(max(float(np.max(np.abs(corrected))), 1e-12) / 127.0)
        q = np.clip(np.rint(corrected / scale), -127, 127).astype(np.int8)
        residual = (corrected - q.astype(np.float32) * scale).astype(np.float32)
        return q, scale, residual

    out = jax.tree.map(_one, host, error_state)
    is_triple = lambda t: isinstance(t, tuple)  # noqa: E731
    payload = {
        "method": "int8_ef",
        "q": jax.tree.map(lambda t: t[0], out, is_leaf=is_triple),
        "scale": jax.tree.map(lambda t: t[1], out, is_leaf=is_triple),
    }
    new_err = jax.tree.map(lambda t: t[2], out, is_leaf=is_triple)
    return payload, new_err


def decompress_tree(payload):
    """Inverse of ``compress_tree``: payload -> fp32 numpy gradient tree."""
    if payload["method"] == "dense":
        return payload["tree"]
    return jax.tree.map(lambda q, s: q.astype(np.float32) * s,
                        payload["q"], payload["scale"])
