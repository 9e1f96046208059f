"""Weights of a dense decoder, made on the device from the run's seed.

The tree has the layout the served model reads (``embed``, ``blocks`` with
one stacked leaf per layer kind, ``final_norm``), built here from the
configuration's sizes alone, so the plain reference reads the very same
numbers without importing the program. One jitted call makes every leaf,
in the dtype the configuration serves, on the default device.

Biases and norm parameters are random rather than zero and one, so a path
that drops a bias or a norm scale shows in the comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def layout(m: dict) -> dict:
    """``{path: (shape, init)}`` for model sizes ``m`` (the configuration's
    ``model`` group); ``init`` is ``("normal", std)`` or ``("scale", std)``
    (1 + std * normal)."""
    d, f, V = m["d_model"], m["d_ff"], m["vocab_size"]
    H, KV, L = m["num_heads"], m["num_kv_heads"], m["num_layers"]
    dh = m.get("head_dim") or d // H
    layernorm = m.get("norm", "rmsnorm") == "layernorm"
    qkv_bias, mlp_bias = m.get("qkv_bias", False), m.get("mlp_bias", False)
    out: dict = {("embed", "tokens"): ((V, d), ("normal", d ** -0.5))}
    if not m.get("tie_embeddings", False):
        out[("embed", "head", "kernel")] = ((d, V), ("normal", d ** -0.5))

    def norm(prefix, lead):
        out[prefix + ("scale",)] = (lead + (d,), ("scale", 0.1))
        if layernorm:
            out[prefix + ("bias",)] = (lead + (d,), ("normal", 0.1))

    def linear(prefix, d_in, d_out, bias):
        out[prefix + ("kernel",)] = ((L, d_in, d_out), ("normal", d_in ** -0.5))
        if bias:
            out[prefix + ("bias",)] = ((L, d_out), ("normal", 0.02))

    blk = ("blocks", "0")
    norm(blk + ("norm",), (L,))
    linear(blk + ("attn", "wq"), d, H * dh, qkv_bias)
    linear(blk + ("attn", "wk"), d, KV * dh, qkv_bias)
    linear(blk + ("attn", "wv"), d, KV * dh, qkv_bias)
    linear(blk + ("attn", "wo"), H * dh, d, False)
    norm(blk + ("mlp_norm",), (L,))
    if m.get("mlp", "swiglu") in ("swiglu", "geglu"):
        linear(blk + ("mlp", "w_gate"), d, f, mlp_bias)
    linear(blk + ("mlp", "w_up"), d, f, mlp_bias)
    linear(blk + ("mlp", "w_down"), f, d, mlp_bias)
    norm(("final_norm",), ())
    return out


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds may pass 32 bits)."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(spec: tuple, key, dtype: str):
    tree: dict = {}
    for i, (path, shape, (kind, std)) in enumerate(spec):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        x = x * std + (1.0 if kind == "scale" else 0.0)
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = x.astype(dtype)
    return tree


def make(m: dict, seed: int, dtype: str = "float32") -> dict:
    """Every leaf of the tree for sizes ``m`` from ``seed``, in ``dtype``."""
    spec = tuple((p, s, i) for p, (s, i) in sorted(layout(m).items()))
    return _make(spec, seed_key(seed), dtype)


def param_count(m: dict, matmul_only: bool = False) -> int:
    """Number of weights; with ``matmul_only``, those that multiply an
    activation (2-D kernels and the output head), as model FLOPs count."""
    n = 0
    for path, (shape, _) in layout(m).items():
        if matmul_only and path[-1] == "kernel":
            n += _prod(shape)
        elif not matmul_only:
            n += _prod(shape)
    if matmul_only and m.get("tie_embeddings", False):
        n += m["vocab_size"] * m["d_model"]
    return n


def _prod(shape) -> int:
    out = 1
    for s in shape:
        out *= s
    return out
