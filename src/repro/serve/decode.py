"""Serving steps: prefill and single-token decode with greedy/temperature
sampling. ``make_serve_step`` is what the dry-run lowers for the
``decode_32k`` / ``long_500k`` shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import transformer
from repro.models.config import ModelConfig


def make_sampler(temperature: float = 0.0, top_k: Optional[int] = None):
    """Build a jit-safe sampler: (logits [B,1,V], key) -> tokens [B,1].

    ``temperature`` / ``top_k`` are *static config* closed over the
    returned function, decided in Python before any trace — the previous
    ``sample_from_logits`` read ``temperature`` with Python truthiness,
    which throws the moment the value is a traced operand (as it would be
    inside the fused decode scan). temperature == 0.0 keeps the exact
    argmax guarantee; otherwise gumbel-max sampling, optionally truncated
    to the ``top_k`` highest logits.
    """
    temperature = float(temperature)

    def sample(logits: jax.Array, key: Optional[jax.Array] = None):
        if temperature <= 0.0 or key is None:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        x = logits.astype(jnp.float32)
        if top_k is not None:
            kth = jax.lax.top_k(x, top_k)[0][..., -1:]
            x = jnp.where(x < kth, -jnp.inf, x)
        noise = jax.random.gumbel(key, x.shape, jnp.float32)
        return jnp.argmax(x / temperature + noise, axis=-1).astype(jnp.int32)

    return sample


def sample_from_logits(logits: jax.Array, key: Optional[jax.Array],
                       temperature: float = 0.0) -> jax.Array:
    """logits [B,1,V] -> tokens [B,1] (compat shim over make_sampler)."""
    return make_sampler(temperature)(logits, key)


def make_serve_step(cfg: ModelConfig, temperature: float = 0.0,
                    top_k: Optional[int] = None, attn_impl: str = "auto"):
    """(params, state, tokens [B,1], t) -> (next_tokens [B,1], new_state)."""
    sampler = make_sampler(temperature, top_k)

    def serve_step(params, state, tokens, t, key=None):
        logits, state = transformer.decode_step(cfg, params, state, tokens,
                                                t, attn_impl=attn_impl)
        return sampler(logits, key), state

    return serve_step


def make_fused_serve_step(cfg: ModelConfig, steps: int,
                          temperature: float = 0.0,
                          top_k: Optional[int] = None,
                          attn_impl: str = "auto"):
    """``steps`` decode+sample iterations fused into ONE executable.

    (params, state, tokens [B,1], t [B], key?) ->
        (token_block [B,steps], new_state, next_tokens [B,1], t + steps,
         next_key)
    and, for a stack of expert-share MoE layers, a sixth output: the
    window's routed token-expert pairs on held experts (int32 scalar).

    The sampler and the per-row feed-token / position updates run inside a
    ``lax.scan``, so the PRNG key, ``tokens`` and ``t`` stay device
    residents and the host syncs once per window instead of once per
    token. Greedy (temperature 0) carries no key (``key=None`` round-trips
    as None). The token block is everything the host needs: EOS /
    ``max_new`` retirement is detected on the sync by slicing each row's
    block to its own stop point — bit-identical to stepping one token at a
    time, because the scan body IS the single-step path.
    """
    sampler = make_sampler(temperature, top_k)
    counts = cfg.expert_share

    def fused(params, state, tokens, t, key=None, pages=None):
        # ``pages`` (paged KV mode) is read-only inside the window — a
        # loop invariant. Threading it into every scan step makes the
        # paged attention path walk the whole working KV through the
        # page table once per step per layer; for multi-step windows the
        # pool is instead materialized as the equivalent flat per-row
        # view ONCE here, the scan runs the flat step body
        # (bit-identical math — the paged oracle is gather + this same
        # computation), and the <= 2 pages per row the window's slots
        # cover scatter back at the end: one pool walk per window
        # instead of ``steps``. A K=1 window (drain tails,
        # sync_every=1) keeps the direct paged step — the view would
        # cost two pool copies for a single token, and the direct path
        # is the one the paged flash-decode kernel serves on TPU.
        # A stack of latent attention only (``paged_in_place``) never
        # takes the view: its decode step reads and writes the pool in
        # place through the page table, so a window costs no pool copy.
        use_view = (pages is not None and steps > 1
                    and not transformer.paged_in_place(cfg))
        pool_state, t0 = state, t
        if use_view:
            state = transformer.paged_window_view(cfg, state, pages)
        step_pages = None if use_view else pages

        def body(carry, _):
            state, tok, t, key, n = carry
            if key is not None:
                key, sub = jax.random.split(key)
            else:
                sub = None
            out = transformer.decode_step(cfg, params, state, tok, t,
                                          attn_impl=attn_impl,
                                          pages=step_pages,
                                          return_counts=counts)
            logits, state = out[:2]
            if counts:
                # Routed pairs on held experts, of rows with a request
                # (pad rows of a compact window sit at t = 0).
                n = n + jnp.where(t > 0, out[2], 0).sum()
            nxt = sampler(logits, sub)
            return (state, nxt, t + 1, key, n), nxt[:, 0]

        n0 = jnp.zeros((), jnp.int32) if counts else None
        (state, tok, t, key, n), toks = jax.lax.scan(
            body, (state, tokens, t, key, n0), None, length=steps)
        if use_view:
            state = transformer.paged_window_scatter(cfg, pool_state, state,
                                                     pages, t0, steps)
        if counts:
            return jnp.moveaxis(toks, 0, 1), state, tok, t, key, n
        return jnp.moveaxis(toks, 0, 1), state, tok, t, key

    return fused


def make_prefill(cfg: ModelConfig, context_len: Optional[int] = None):
    def prefill_step(params, tokens, memory=None, embeddings=None):
        return transformer.prefill(
            cfg, params, tokens=tokens, memory=memory, embeddings=embeddings,
            context_len=context_len)
    return prefill_step


# Jitted-executable caches: make_serve_step/make_prefill return fresh
# closures, so a bare jax.jit around them would recompile on EVERY
# generate() call — ~seconds per serving batch, dwarfing the actual step.
# Keyed on the full static config (cfg, temperature, top_k, attn_impl /
# context_len); ModelConfig is frozen. attn_impl MUST be part of the key:
# the kernel-vs-dense choice is baked in at trace time.
@functools.lru_cache(maxsize=None)
def _cached_step(cfg: ModelConfig, temperature: float,
                 top_k: Optional[int] = None, attn_impl: str = "auto"):
    return jax.jit(make_serve_step(cfg, temperature, top_k, attn_impl))


@functools.lru_cache(maxsize=None)
def cached_fused_step(cfg: ModelConfig, steps: int, temperature: float,
                      top_k: Optional[int] = None, attn_impl: str = "auto"):
    """Shared fused-window executables (engines come and go; the compiled
    K-step scan is reusable across instances). state/tokens/t are donated:
    the engine threads them through as device residents."""
    return jax.jit(make_fused_serve_step(cfg, steps, temperature, top_k,
                                         attn_impl),
                   donate_argnums=(1, 2, 3))


@functools.lru_cache(maxsize=None)
def _cached_prefill(cfg: ModelConfig, context_len: int):
    return jax.jit(make_prefill(cfg, context_len))


_MASKABLE = {"attn", "swa", "local", "xattn"}


def _check_ragged_supported(cfg: ModelConfig, S: int, context_len: int):
    kinds = set(cfg.pattern) | set(cfg.remainder)
    if kinds - _MASKABLE:
        raise ValueError(
            f"ragged generate (lengths=...) needs an attention-only stack; "
            f"{cfg.name} has {sorted(kinds - _MASKABLE)} blocks whose "
            "recurrent state would absorb the pad tokens. Serve those "
            "architectures through the engine (exact-length prefill) or "
            "with equal-length prompts.")
    if context_len < S or (cfg.window is not None and cfg.window < S):
        raise ValueError(
            f"ragged generate needs the KV ring (context_len={context_len}, "
            f"window={cfg.window}) to hold the padded prompt (S={S}): a "
            "shorter ring wraps pad K/V onto slots the position mask "
            "treats as valid history.")


def generate(cfg: ModelConfig, params, prompt: jax.Array, max_new: int,
             context_len: Optional[int] = None, temperature: float = 0.0,
             key: Optional[jax.Array] = None, memory=None,
             lengths: Optional[jax.Array] = None,
             top_k: Optional[int] = None, attn_impl: str = "auto"):
    """Convenience loop for examples/tests: prefill + greedy decode.

    prompt [B, S] -> tokens [B, S + max_new].

    ``lengths`` ([B] int, optional) marks the true length of each
    right-padded row. With it, row ``b``'s continuation is sampled from the
    logits at its own last real token and decoded at per-row positions
    ``lengths[b] + i`` — pad K/V beyond a row's length sits at ring slots
    the decode position mask rejects (and decode overwrites them in order),
    so a padded row matches the same prompt served alone instead of
    attending to pad tokens as context. Generated tokens land at
    ``out[b, lengths[b]:lengths[b]+max_new]``; the tail keeps the pad.
    """
    import numpy as np
    B, S = prompt.shape
    context_len = context_len or (S + max_new)
    if lengths is not None and bool((np.asarray(lengths) == S).all()):
        lengths = None          # nothing is padded: every stack serves this
    if lengths is not None:
        _check_ragged_supported(cfg, S, context_len)
        t0 = jnp.asarray(lengths, jnp.int32)
    else:
        t0 = jnp.full((B,), S, jnp.int32)
    if memory is None:
        logits, state = _cached_prefill(cfg, context_len)(params,
                                                          tokens=prompt)
    else:  # VLM memory is test-only; skip the executable cache
        logits, state = transformer.prefill(cfg, params, tokens=prompt,
                                            memory=memory,
                                            context_len=context_len)
    last_logits = jnp.take_along_axis(logits, (t0 - 1)[:, None, None], axis=1)
    sampler = make_sampler(temperature, top_k)
    last = sampler(last_logits, key)
    step = _cached_step(cfg, temperature, top_k, attn_impl)
    gen = [last]
    tok = last
    for i in range(max_new - 1):
        if key is not None:
            key, sub = jax.random.split(key)
        else:
            sub = None
        tok, state = step(params, state, tok, t0 + i, sub)
        gen.append(tok)
    gen = jnp.concatenate(gen, axis=1)                     # [B, max_new]
    out = jnp.zeros((B, S + max_new), prompt.dtype).at[:, :S].set(prompt)
    cols = t0[:, None] + jnp.arange(max_new, dtype=jnp.int32)[None, :]
    return out.at[jnp.arange(B)[:, None], cols].set(gen)
