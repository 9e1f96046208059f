"""Operations and bytes that the work needs, from the model's sizes.

Model FLOPs count two operations for each weight that multiplies an
activation (projections, MLP and output head) per token (``matmul``: how
many there are, as the configuration's weight maker counts them), plus the
attention products (q·k and w·v: four per head dimension per key seen).
A key is seen when it lies at or before the query and within the window.
Bytes of decode attention are the least the flash-decode kernels must read:
each live K and V entry once, in bfloat16 (as ``benchmarks/kernel_bench.py``
counts them), plus the query and output rows.
"""

from __future__ import annotations

KV_BYTES = 2                # bfloat16 cache entries


def _heads(m: dict) -> tuple[int, int, int, int]:
    H, KV = m["num_heads"], m["num_kv_heads"]
    return m["num_layers"], H, KV, m.get("head_dim") or m["d_model"] // H


def _window(m: dict) -> int | None:
    return m.get("window") if m.get("pattern", ["attn"])[0] == "swa" else None


def keys_seen(m: dict, t: int) -> int:
    """Keys that the query at position ``t`` attends to."""
    w = _window(m)
    return min(t + 1, w) if w else t + 1


def _keys_sum(m: dict, lo: int, hi: int) -> int:
    """Sum of ``keys_seen`` over positions ``lo <= t < hi``."""
    w = _window(m)
    if hi <= lo:
        return 0
    if not w:
        return (hi * (hi + 1) - lo * (lo + 1)) // 2
    cut = max(lo, min(hi, w))       # positions below ``cut`` see t + 1 keys
    return (cut * (cut + 1) - lo * (lo + 1)) // 2 + (hi - cut) * w


def sequence_flops(m: dict, matmul: int, lo: int, hi: int) -> float:
    """Model FLOPs of the tokens at positions ``lo <= t < hi`` of one
    sequence (prefill of a prompt is ``0, P``; its decode ``P, P + n``)."""
    L, H, _, dh = _heads(m)
    return 2.0 * matmul * (hi - lo) + 4.0 * L * H * dh * _keys_sum(m, lo, hi)


def request_flops(m: dict, matmul: int, prompt: int, new: int) -> float:
    """Prefill of ``prompt`` tokens plus the ``new - 1`` decode steps that
    follow the first token (which the prefill itself produces)."""
    return sequence_flops(m, matmul, 0, prompt + max(new - 1, 0))


def decode_attn_bytes(m: dict, lo: int, hi: int) -> float:
    """Least HBM bytes the decode attention kernels read for the decode
    steps at positions ``lo <= t < hi``, over all layers."""
    L, H, KV, dh = _heads(m)
    kv = 2.0 * KV * dh * KV_BYTES * _keys_sum(m, lo, hi)
    qo = 2.0 * H * dh * KV_BYTES * (hi - lo)
    return L * (kv + qo)


def decode_attn_flops(m: dict, lo: int, hi: int) -> float:
    L, H, _, dh = _heads(m)
    return 4.0 * L * H * dh * _keys_sum(m, lo, hi)
