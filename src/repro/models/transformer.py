"""Backbone assembly: superblock pattern -> scan over repeats -> LM heads.

The layer stack is ``cfg.first_dense`` unrolled leading layers (``lead``:
kind ``pattern[0]`` with the dense MLP, DeepSeek's first_k_dense_replace),
then ``cfg.pattern`` repeated ``cfg.num_repeats`` times (with stacked params
under ``jax.lax.scan``), then an unrolled remainder (``tail``). The same
block functions serve training/prefill (full sequence) and decode (single
token + recurrent/KV state).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mla, moe, rglru, ssm
from repro.models.config import (ATTN, LOCAL, MAMBA, MLA, PAGED_KINDS, RGLRU,
                                 SWA, XATTN, ModelConfig)
from repro.sharding import shard

# When num_repeats <= this threshold the repeat loop is unrolled in Python
# instead of lax.scan. The roofline cost-probe sets it (scan/while bodies
# are counted ONCE by XLA cost analysis, so per-layer costs must come from
# unrolled compiles); production configs keep scan for compile-time/HLO-size
# independence from depth.
SCAN_UNROLL_THRESHOLD = 0


def _repeat_blocks(body, carry, stacked_params, extra=None):
    """lax.scan over stacked superblocks, or an unrolled Python loop."""
    length = jax.tree.leaves(stacked_params)[0].shape[0]
    xs = stacked_params if extra is None else (stacked_params, extra)
    if length <= SCAN_UNROLL_THRESHOLD:
        ys = []
        for i in range(length):
            xi = jax.tree.map(lambda a: a[i], xs)
            carry, y = body(carry, xi)
            ys.append(y)
        if ys and ys[0] is not None:
            ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
        else:
            ys = None
        return carry, ys
    return jax.lax.scan(body, carry, xs)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _groups(cfg: ModelConfig):
    """(group, kinds, axis of a state leaf's rows) of each layer group in
    stack order; ``blocks`` leaves carry the repeat axis first."""
    return (("lead", cfg.lead, 0), ("blocks", cfg.pattern, 1),
            ("tail", cfg.remainder, 0))


def paged_in_place(cfg: ModelConfig) -> bool:
    """Whether paged decode reads and writes the page pool in place (every
    layer latent attention), rather than through a flat per-row view."""
    return cfg.kinds == {MLA}


def _init_mlp(cfg: ModelConfig, key, dense: bool) -> dict:
    if dense or not cfg.num_experts:
        return layers.init_mlp(cfg, key)
    if cfg.expert_share:
        return moe.init_expert_share(cfg, key)
    return moe.init_moe(cfg, key)


def _mlp(cfg: ModelConfig, p: dict, h: jax.Array, dense: bool = False):
    """(out, aux loss, routed pairs on held experts per token or None)."""
    if dense or not cfg.num_experts:
        return layers.apply_mlp(cfg, p, h), None, None
    if cfg.expert_share:
        y, pairs = moe.apply_expert_share(cfg, p, h)
        return y, None, pairs
    y, aux = moe.apply_moe(cfg, p, h)
    return y, aux, None


def _init_block(cfg: ModelConfig, kind: str, key, dense: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"norm": layers.init_norm(cfg)}
    if kind == MLA:
        p["attn"] = mla.init_mla(cfg, ks[0])
        p["mlp_norm"] = layers.init_norm(cfg)
        p["mlp"] = _init_mlp(cfg, ks[1], dense)
    elif kind in (ATTN, SWA, LOCAL, XATTN):
        p["attn"] = attention.init_attention(cfg, ks[0], cross=(kind == XATTN))
        p["mlp_norm"] = layers.init_norm(cfg)
        if cfg.num_experts:
            p["mlp"] = moe.init_moe(cfg, ks[1])
        else:
            p["mlp"] = layers.init_mlp(cfg, ks[1])
    elif kind == RGLRU:
        p["rglru"] = rglru.init_rglru_block(cfg, ks[0])
        p["mlp_norm"] = layers.init_norm(cfg)
        p["mlp"] = layers.init_mlp(cfg, ks[1])
    elif kind == MAMBA:
        p["mamba"] = ssm.init_mamba_block(cfg, ks[0])
    else:
        raise ValueError(kind)
    return p


def _init_superblock(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, len(cfg.pattern))
    return {str(i): _init_block(cfg, kind, ks[i])
            for i, kind in enumerate(cfg.pattern)}


def init_params(cfg: ModelConfig, key) -> dict:
    k_embed, k_blocks, k_tail = jax.random.split(key, 3)
    params: dict[str, Any] = {"embed": layers.init_embed(cfg, k_embed)}
    if cfg.lead:
        lead_keys = jax.random.split(jax.random.fold_in(k_tail, 1),
                                     len(cfg.lead))
        params["lead"] = {str(i): _init_block(cfg, kind, lead_keys[i],
                                              dense=True)
                          for i, kind in enumerate(cfg.lead)}
    if cfg.num_repeats:
        rep_keys = jax.random.split(k_blocks, cfg.num_repeats)
        params["blocks"] = jax.vmap(
            lambda k: _init_superblock(cfg, k))(rep_keys)
    if cfg.remainder:
        tail_keys = jax.random.split(k_tail, len(cfg.remainder))
        params["tail"] = {str(i): _init_block(cfg, kind, tail_keys[i])
                          for i, kind in enumerate(cfg.remainder)}
    params["final_norm"] = layers.init_norm(cfg)
    return params


def param_shapes(cfg: ModelConfig) -> Any:
    """Abstract param tree (no allocation) — used by the dry-run."""
    return jax.eval_shape(
        lambda k: init_params(cfg, k), jax.random.key(0))


# MoE expert stacks: bare [E, ...] arrays that moe.apply_moe casts before
# its einsums (a dense MLP keeps linear dicts under the same names).
_EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def serving_params(cfg: ModelConfig, params: dict) -> dict:
    """The weights a server decodes from: ``params`` with every leaf that
    all its readers cast to the compute dtype before use already in it.

    Those are a linear layer's ``kernel`` and ``bias``
    (``layers.apply_linear``: attention, MLP, MoE router, recurrent and
    SSM projections, the untied head), the embedding table
    (``embed_tokens`` and the tied ``lm_logits``) and MoE expert stacks.
    Every other leaf keeps its dtype: norm scales and biases (read in
    float32), q/k norms, and the RG-LRU and SSM leaves outside linear
    layers (gates, Λ, A, D, conv weights), which are small or enter
    float32 arithmetic. A step therefore computes with the same rounded
    values as from the masters, with the cast paid once here instead of
    in every compiled call. A leaf already in the compute dtype is
    returned as the same array, so bfloat16 masters cost no copy.
    """
    dt = layers.cdtype(cfg)

    def cast(x):
        return x if x.dtype == dt else x.astype(dt)

    def walk(node: dict, name: str) -> dict:
        out = {}
        for k, v in node.items():
            if isinstance(v, dict):
                out[k] = walk(v, k)
            elif ((k in ("kernel", "bias") and "kernel" in node)
                  or (name == "embed" and k == "tokens")
                  or k in _EXPERT_STACKS):
                out[k] = cast(v)
            else:
                out[k] = v
        return out

    return walk(params, "")


# ---------------------------------------------------------------------------
# Full-sequence block application (train / prefill)
# ---------------------------------------------------------------------------

def _apply_block(cfg: ModelConfig, kind: str, p: dict, x: jax.Array,
                 positions: jax.Array, memory: Optional[jax.Array],
                 dense: bool = False):
    """Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = layers.apply_norm(cfg, p["norm"], x)
    if kind == MLA:
        h = mla.self_attention(cfg, p["attn"], h, positions)
        x = x + h
        h, a, _ = _mlp(cfg, p["mlp"], layers.apply_norm(cfg, p["mlp_norm"], x),
                       dense)
        return x + h, aux if a is None else a
    if kind in (ATTN, SWA, LOCAL):
        h = attention.self_attention(cfg, p["attn"], h, positions, kind)
    elif kind == XATTN:
        h = attention.cross_attention(cfg, p["attn"], h, memory)
    elif kind == RGLRU:
        h, _ = rglru.apply_rglru_block(cfg, p["rglru"], h)
    elif kind == MAMBA:
        h, _ = ssm.apply_mamba_block(cfg, p["mamba"], h)
    x = x + h
    x = shard(x, "dp", None, None)
    if kind != MAMBA:
        h = layers.apply_norm(cfg, p["mlp_norm"], x)
        if cfg.num_experts:
            h, aux = moe.apply_moe(cfg, p["mlp"], h)
        else:
            h = layers.apply_mlp(cfg, p["mlp"], h)
        x = x + h
        x = shard(x, "dp", None, None)
    return x, aux


def _apply_superblock(cfg: ModelConfig, p: dict, x: jax.Array,
                      positions: jax.Array, memory: Optional[jax.Array]):
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(cfg.pattern):
        x, a = _apply_block(cfg, kind, p[str(i)], x, positions, memory)
        aux = aux + a
    return x, aux


def forward(cfg: ModelConfig, params: dict, *,
            tokens: Optional[jax.Array] = None,
            embeddings: Optional[jax.Array] = None,
            memory: Optional[jax.Array] = None,
            remat: bool = False,
            resid_tp: bool = False) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (hidden [B,S,D], aux_loss).

    ``resid_tp`` feature-shards the residual stream at superblock
    boundaries (FSDP+SP): the tensors remat saves for backward shrink by
    the TP width at the cost of per-layer feature all-gathers.
    """
    if embeddings is not None:
        x = embeddings.astype(layers.cdtype(cfg))     # audio frontend stub
    else:
        x = layers.embed_tokens(cfg, params["embed"], tokens)
    x = layers.add_conv_pos(cfg, params["embed"], x)
    resid_spec = ("dp", None, "tp") if resid_tp else ("dp", None, None)
    x = shard(x, *resid_spec)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if memory is not None:
        memory = memory.astype(x.dtype)

    aux_total = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(cfg.lead):
        x, _ = _apply_block(cfg, kind, params["lead"][str(i)], x, positions,
                            memory, dense=True)

    def sb_fn(blk_params, h, positions, memory):
        h, a = _apply_superblock(cfg, blk_params, h, positions, memory)
        return shard(h, *resid_spec), a
    if remat:
        sb_fn = jax.checkpoint(
            sb_fn, policy=jax.checkpoint_policies.nothing_saveable)

    if "blocks" in params:
        def body(carry, blk_params):
            h, aux = carry
            h, a = sb_fn(blk_params, h, positions, memory)
            return (h, aux + a), None
        (x, aux_total), _ = _repeat_blocks(body, (x, aux_total),
                                           params["blocks"])

    if "tail" in params:
        for i, kind in enumerate(cfg.remainder):
            x, a = _apply_block(cfg, kind, params["tail"][str(i)], x,
                                positions, memory)
            aux_total = aux_total + a

    x = layers.apply_norm(cfg, params["final_norm"], x)
    return x, aux_total


def logits_from_hidden(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    logits = layers.lm_logits(cfg, params["embed"], x)
    return shard(logits, "dp", None, "tp")


def cross_entropy(cfg: ModelConfig, logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Vocab-sharding-friendly CE: one-hot contraction, no gather."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, cfg.vocab_size, dtype=logits.dtype)
    true_logit = jnp.einsum("bsv,bsv->bs", logits, onehot)
    nll = lse - true_logit
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()


def loss_fn(cfg: ModelConfig, params: dict, batch: dict,
            remat: bool = False, resid_tp: bool = False
            ) -> tuple[jax.Array, dict]:
    """Language-model / masked-prediction loss over one (micro)batch."""
    hidden, aux = forward(
        cfg, params,
        tokens=batch.get("tokens"),
        embeddings=batch.get("embeddings"),
        memory=batch.get("image_embeds"),
        remat=remat, resid_tp=resid_tp)
    logits = logits_from_hidden(cfg, params, hidden)
    if cfg.causal and "targets" not in batch:
        # Next-token prediction: shift within the provided sequence.
        ce = cross_entropy(cfg, logits[:, :-1], batch["labels"][:, 1:],
                           batch.get("mask")[:, 1:] if batch.get("mask")
                           is not None else None)
    else:
        # Encoder (HuBERT): predict per-position targets at masked frames.
        ce = cross_entropy(cfg, logits, batch["targets"], batch.get("mask"))
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Prefill: full-sequence pass that also builds the decode state
# ---------------------------------------------------------------------------

def _prefill_block(cfg: ModelConfig, kind: str, p: dict, x: jax.Array,
                   positions: jax.Array, memory: Optional[jax.Array],
                   context_len: int, cache_dtype, dense: bool = False):
    """Like _apply_block but also returns the block's decode state."""
    h = layers.apply_norm(cfg, p["norm"], x)
    if kind == MLA:
        h, entries = mla.self_attention(cfg, p["attn"], h, positions,
                                        return_cache=True)
        x = x + h
        h, _, _ = _mlp(cfg, p["mlp"], layers.apply_norm(cfg, p["mlp_norm"], x),
                       dense)
        return x + h, mla.build_cache(entries, context_len, cache_dtype)
    if kind in (ATTN, SWA, LOCAL):
        h, (k, v) = attention.self_attention(cfg, p["attn"], h, positions,
                                             kind, return_kv=True)
        state = attention.build_cache_from_full(cfg, k, v, context_len, kind,
                                                cache_dtype)
    elif kind == XATTN:
        h = attention.cross_attention(cfg, p["attn"], h, memory)
        _, k_mem, v_mem = attention._project_qkv(cfg, p["attn"],
                                                 h[:, :1], memory)
        state = {"k_mem": k_mem.astype(cache_dtype),
                 "v_mem": v_mem.astype(cache_dtype)}
    elif kind == RGLRU:
        h, state = rglru.apply_rglru_block(cfg, p["rglru"], h,
                                           want_state=True)
    elif kind == MAMBA:
        h, state = ssm.apply_mamba_block(cfg, p["mamba"], h, want_state=True)
    else:
        raise ValueError(kind)
    x = x + h
    if kind != MAMBA:
        h = layers.apply_norm(cfg, p["mlp_norm"], x)
        if cfg.num_experts:
            h, _ = moe.apply_moe(cfg, p["mlp"], h)
        else:
            h = layers.apply_mlp(cfg, p["mlp"], h)
        x = x + h
    return x, state


def prefill(cfg: ModelConfig, params: dict, *, tokens=None, memory=None,
            embeddings=None, context_len: Optional[int] = None,
            cache_dtype=jnp.bfloat16):
    """Full-sequence forward that also builds the decode state.

    Returns (logits [B,S,V], decode_state positioned at t = S).
    """
    if embeddings is not None:
        x = embeddings.astype(layers.cdtype(cfg))
    else:
        x = layers.embed_tokens(cfg, params["embed"], tokens)
    x = layers.add_conv_pos(cfg, params["embed"], x)
    x = shard(x, "dp", None, None)
    B, S = x.shape[:2]
    context_len = context_len or S
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if memory is not None:
        memory = memory.astype(x.dtype)

    state: dict[str, Any] = {}
    if cfg.lead:
        state["lead"] = {}
        for i, kind in enumerate(cfg.lead):
            x, state["lead"][str(i)] = _prefill_block(
                cfg, kind, params["lead"][str(i)], x, positions, memory,
                context_len, cache_dtype, dense=True)
    if "blocks" in params:
        def body(h, blk_params):
            blk_state = {}
            for i, kind in enumerate(cfg.pattern):
                h, s = _prefill_block(cfg, kind, blk_params[str(i)], h,
                                      positions, memory, context_len,
                                      cache_dtype)
                blk_state[str(i)] = s
            return h, blk_state
        x, state["blocks"] = _repeat_blocks(body, x, params["blocks"])

    if "tail" in params:
        state["tail"] = {}
        for i, kind in enumerate(cfg.remainder):
            x, s = _prefill_block(cfg, kind, params["tail"][str(i)], x,
                                  positions, memory, context_len, cache_dtype)
            state["tail"][str(i)] = s

    x = layers.apply_norm(cfg, params["final_norm"], x)
    logits = layers.lm_logits(cfg, params["embed"], x)
    return shard(logits, "dp", None, "tp"), state


# ---------------------------------------------------------------------------
# Decode: single-token step with per-layer state
# ---------------------------------------------------------------------------

def _block_state_spec(cfg: ModelConfig, kind: str, batch: int,
                      context_len: int, dtype,
                      page_size: Optional[int] = None,
                      num_pages: Optional[int] = None) -> dict:
    if kind == MLA:
        if page_size is not None:
            return mla.cache_spec(cfg, num_pages, page_size, dtype)
        return mla.cache_spec(cfg, batch, context_len, dtype)
    if kind == ATTN and page_size is not None:
        return attention.paged_kv_cache_spec(cfg, num_pages, page_size,
                                             dtype)
    if kind in (ATTN, SWA, LOCAL):
        return attention.kv_cache_spec(cfg, batch, context_len, kind, dtype)
    if kind == XATTN:
        shape = (batch, cfg.frontend_tokens, cfg.num_kv_heads, cfg.head_dim)
        return {"k_mem": jax.ShapeDtypeStruct(shape, dtype),
                "v_mem": jax.ShapeDtypeStruct(shape, dtype)}
    if kind == RGLRU:
        return rglru.rglru_state_spec(cfg, batch)
    if kind == MAMBA:
        return ssm.mamba_state_spec(cfg, batch)
    raise ValueError(kind)


def decode_state_spec(cfg: ModelConfig, batch: int, context_len: int,
                      dtype=jnp.bfloat16, page_size: Optional[int] = None,
                      num_pages: Optional[int] = None) -> dict:
    """Abstract decode-state tree matching decode_step's expectations.

    With ``page_size``/``num_pages`` set, full-context ATTN layers swap
    their per-row ``[batch, L]`` rings for one shared ``[num_pages,
    page_size]`` pool addressed through a per-row page table (see
    ``decode_step``'s ``pages``). Windowed rings (SWA/LOCAL) and
    recurrent/XATTN state stay per-row: their footprint is already
    bounded, so paging buys nothing there.
    """
    def stack(spec_fn):
        one = spec_fn()
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.num_repeats,) + s.shape,
                                           s.dtype), one)

    state: dict[str, Any] = {}
    if cfg.lead:
        state["lead"] = {
            str(i): _block_state_spec(cfg, kind, batch, context_len, dtype,
                                      page_size, num_pages)
            for i, kind in enumerate(cfg.lead)}
    if cfg.num_repeats:
        state["blocks"] = {
            str(i): stack(functools.partial(
                _block_state_spec, cfg, kind, batch, context_len, dtype,
                page_size, num_pages))
            for i, kind in enumerate(cfg.pattern)}
    if cfg.remainder:
        state["tail"] = {
            str(i): _block_state_spec(cfg, kind, batch, context_len, dtype,
                                      page_size, num_pages)
            for i, kind in enumerate(cfg.remainder)}
    return state


def init_decode_state(cfg: ModelConfig, batch: int, context_len: int,
                      dtype=jnp.bfloat16, page_size: Optional[int] = None,
                      num_pages: Optional[int] = None) -> dict:
    spec = decode_state_spec(cfg, batch, context_len, dtype, page_size,
                             num_pages)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)


def write_decode_slot(cfg: ModelConfig, state: dict, slot_state: dict,
                      index) -> dict:
    """Write a batch-1 decode-state tree into row ``index`` of a batched one.

    ``state`` is the engine's slotted cache (``init_decode_state`` with
    batch = num_slots); ``slot_state`` comes from ``prefill`` over a
    ``[1, S]`` prompt with the same ``context_len``. Leaves under "blocks"
    carry the stacked repeat dim first (batch is axis 1); "tail" leaves
    are batch-leading (axis 0). ``index`` may be traced, so a jitted
    wrapper (ideally donating ``state``) admits a request into a free slot
    without touching the other rows.
    """
    def _write(axis):
        def f(dst, src):
            return jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), index, axis=axis)
        return f

    out: dict[str, Any] = {}
    if "lead" in state:
        out["lead"] = jax.tree.map(_write(0), state["lead"],
                                   slot_state["lead"])
    if "blocks" in state:
        out["blocks"] = jax.tree.map(_write(1), state["blocks"],
                                     slot_state["blocks"])
    if "tail" in state:
        out["tail"] = jax.tree.map(_write(0), state["tail"],
                                   slot_state["tail"])
    return out


def _paged_leaf_write(dst: jax.Array, src: jax.Array, row_pages: jax.Array,
                      start_page: jax.Array, page_size: int,
                      page_axis: int) -> jax.Array:
    """Scatter a B=1 flat cache leaf into the shared page pool.

    ``dst`` has physical pages on ``page_axis``; ``src`` is the flat leaf
    with its batch-1 axis at ``page_axis`` and the L_pad sequence right
    after it, so merging that axis with a ``[n_log, page_size]`` split of
    the sequence gives one update block per logical page — the whole row
    lands in a single gather + scatter instead of a per-page
    dynamic-update chain. Logical pages below ``start_page`` are *shared*
    (copy-on-write prefix pages another owner may also read): their pool
    content is rewritten with itself, so the write is a no-op there
    without a traced-shape branch.
    """
    n_log = row_pages.shape[0]
    seq_axis = page_axis + 1
    shape = (src.shape[:page_axis] + (n_log, page_size)
             + src.shape[seq_axis + 1:])
    sp = src.reshape(shape).astype(dst.dtype)       # batch-1 axis -> pages
    cur = jnp.take(dst, row_pages, axis=page_axis)
    keep = jnp.arange(n_log, dtype=jnp.int32) >= start_page
    kshape = ((1,) * page_axis + (n_log,)
              + (1,) * (sp.ndim - page_axis - 1))
    upd = jnp.where(keep.reshape(kshape), sp, cur)
    if page_axis == 0:
        return dst.at[row_pages].set(upd)
    return dst.at[:, row_pages].set(upd)            # stacked repeat leads


def _stacked_pool_write(dst: jax.Array, src: jax.Array, row_pages: jax.Array,
                        start_page: jax.Array, page_size: int) -> jax.Array:
    """``_paged_leaf_write`` for a pool with a leading layer axis, one
    layer at a time: XLA lays out a gather or scatter across the page axis
    of the whole stack by copying the stack (a latent pool is most of the
    device), and one layer's pages cost no such copy."""
    n_log = row_pages.shape[0]
    sp = src.reshape((src.shape[0], n_log, page_size) + src.shape[3:])
    keep = (jnp.arange(n_log, dtype=jnp.int32) >= start_page).reshape(
        (n_log,) + (1,) * (sp.ndim - 2))

    def one(d, i):
        upd = jnp.where(keep, sp[i].astype(d.dtype), d[i][row_pages])
        return d.at[i, row_pages].set(upd), None
    return jax.lax.scan(one, dst, jnp.arange(dst.shape[0]))[0]


def write_paged_slot(cfg: ModelConfig, state: dict, slot_state: dict,
                     index, row_pages: jax.Array, start_page,
                     page_size: int) -> dict:
    """Paged counterpart of ``write_decode_slot``: land a B=1 prefill
    state into row ``index``, scattering full-context ATTN leaves into the
    shared page pool through the row's page list.

    ``row_pages`` [n_log] int32 maps logical page j -> physical page;
    entries past the row's reservation point at the trash page (0), whose
    content only trash reads see. ``start_page`` (traced scalar) is the
    count of leading *shared* prefix pages: those already hold exactly the
    prefill content being written, so they are skipped (copy-on-write —
    the pool rows other owners read are never touched). Non-ATTN leaves
    (windowed rings, recurrent state) write per-row exactly as
    ``write_decode_slot`` does.
    """
    start_page = jnp.asarray(start_page, jnp.int32)

    def _write_kind(kind: str, dst, src, axis: int):
        if kind == MLA and axis == 1:
            return jax.tree.map(
                lambda d, s: _stacked_pool_write(d, s, row_pages, start_page,
                                                 page_size), dst, src)
        if kind in PAGED_KINDS:
            return jax.tree.map(
                lambda d, s: _paged_leaf_write(d, s, row_pages, start_page,
                                               page_size, axis), dst, src)
        return jax.tree.map(
            lambda d, s: jax.lax.dynamic_update_slice_in_dim(
                d, s.astype(d.dtype), index, axis=axis), dst, src)

    return {g: {str(i): _write_kind(kind, state[g][str(i)],
                                    slot_state[g][str(i)], axis)
                for i, kind in enumerate(kinds)}
            for g, kinds, axis in _groups(cfg) if g in state}


def gather_paged_slot(cfg: ModelConfig, state: dict, index,
                      row_pages: jax.Array, page_size: int) -> dict:
    """Materialize row ``index`` of a paged decode state as a B=1 *flat*
    state (the shape ``prefill_extend`` consumes): ATTN leaves gather the
    row's page list into its logical [1, L_pad] cache view; other leaves
    slice the row. Used on a prefix-cache hit — the gathered view holds
    the shared prefix K/V, the suffix extends it, and ``write_paged_slot``
    (start_page = shared count) scatters only the owned pages back.
    """
    n_log = row_pages.shape[0]

    def _gather_kind(kind: str, leaf, axis: int):
        if kind in PAGED_KINDS:
            def g(pool):
                if kind == MLA and axis == 1:   # a layer at a time, as above
                    out = jax.lax.map(lambda p: p[row_pages], pool)
                else:
                    out = jnp.take(pool, row_pages, axis=axis)
                shape = (pool.shape[:axis] + (1, n_log * page_size)
                         + pool.shape[axis + 2:])
                return out.reshape(shape)
            return jax.tree.map(g, leaf)
        return jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, index, 1, axis=axis),
            leaf)

    return {g: {str(i): _gather_kind(kind, state[g][str(i)], axis)
                for i, kind in enumerate(kinds)}
            for g, kinds, axis in _groups(cfg) if g in state}


def paged_window_view(cfg: ModelConfig, state: dict,
                      pages: jax.Array) -> dict:
    """Gather a paged decode state into the equivalent flat per-row view.

    Full-context ATTN pool leaves ([..., P+1, ps, KV, dh]) become the
    flat rings decode_step's non-paged path expects ([..., B, L_pad, KV,
    dh], L_pad = n_log * page_size) by walking each row's page list;
    every other leaf is already per-row and passes through untouched.
    The page table is invariant inside a fused decode window, so doing
    this ONCE per window — instead of re-gathering the pool inside every
    scan step, as the paged attention path must — is what lets the paged
    engine pay ~flat per-step cost; ``paged_window_scatter`` lands the
    window's writes back in the pool afterwards.
    """
    B, n_log = pages.shape

    def _gather_kind(kind: str, leaf, axis: int):
        if kind not in PAGED_KINDS:
            return leaf

        def g(pool):
            ps = pool.shape[axis + 1]
            out = jnp.take(pool, pages, axis=axis)
            shape = (pool.shape[:axis] + (B, n_log * ps)
                     + pool.shape[axis + 2:])
            return out.reshape(shape)
        return jax.tree.map(g, leaf)

    return {g: {str(i): _gather_kind(kind, state[g][str(i)], axis)
                for i, kind in enumerate(kinds)}
            for g, kinds, axis in _groups(cfg) if g in state}


def paged_window_scatter(cfg: ModelConfig, state: dict, flat: dict,
                         pages: jax.Array, t0: jax.Array,
                         steps: int) -> dict:
    """Inverse of ``paged_window_view`` after a ``steps``-long window.

    Decode positions ``t0[b] .. t0[b]+steps-1`` land in at most
    ``1 + ceil((steps-1)/ps)`` consecutive logical pages per row, so only
    those pages scatter back into the pool — everything else in the flat
    view is byte-identical to what the gather read. Pages inside the
    static bound that the window did not actually reach get identity
    writes (their flat content IS the pool content), which is what keeps
    shared copy-on-write prefix pages safe: a row's decode positions
    start at its prompt end, past every fully-covered shared page, so
    real writes only ever land in owned (or trash) pages. Rows whose
    table is all trash (free slots) dogpile page 0 — undefined winner,
    read by nobody. Non-ATTN leaves are per-row state the scan already
    updated in place; they pass through from the flat tree.
    """
    B, n_log = pages.shape
    t0 = jnp.asarray(t0, jnp.int32)
    if t0.ndim == 0:
        t0 = jnp.full((B,), t0)

    def _scatter_kind(kind: str, pool_leaf, flat_leaf, axis: int):
        if kind not in PAGED_KINDS:
            return flat_leaf

        def s(pool, fl):
            ps = pool.shape[axis + 1]
            L = n_log * ps
            ntouch = min(n_log, 1 + (max(steps - 1, 0) + ps - 1) // ps)
            j0 = (t0 % L) // ps
            jj = (j0[:, None]
                  + jnp.arange(ntouch, dtype=jnp.int32)[None, :]) % n_log
            pid = jnp.take_along_axis(pages, jj, axis=1)        # [B, C]
            shape = (fl.shape[:axis] + (B, n_log, ps)
                     + fl.shape[axis + 2:])
            fr = fl.reshape(shape)
            bb = jnp.arange(B, dtype=jnp.int32)[:, None]
            if axis == 0:
                return pool.at[pid].set(fr[bb, jj].astype(pool.dtype))
            return pool.at[:, pid].set(fr[:, bb, jj].astype(pool.dtype))
        return jax.tree.map(s, pool_leaf, flat_leaf)

    return {g: {str(i): _scatter_kind(kind, state[g][str(i)],
                                      flat[g][str(i)], axis)
                for i, kind in enumerate(kinds)}
            for g, kinds, axis in _groups(cfg) if g in state}


def _decode_block(cfg: ModelConfig, kind: str, p: dict, x: jax.Array,
                  state: dict, t: jax.Array, attn_impl: str = "auto",
                  pages: Optional[jax.Array] = None, layer=None,
                  dense: bool = False):
    """Returns (x, state, routed pairs on held experts [B,1] or None).
    For latent attention over the page pool, ``state`` is the group's
    whole pool (a leading layer axis when ``layer`` is given)."""
    h = layers.apply_norm(cfg, p["norm"], x)
    if kind == MLA:
        if pages is None:
            h, state = mla.decode_attention(cfg, p["attn"], h, state, t)
        else:
            pool = state["ckv"] if layer is not None else state["ckv"][None]
            h, pool = mla.paged_decode_attention(
                cfg, p["attn"], h, pool, 0 if layer is None else layer, t,
                pages, impl=attn_impl)
            state = {"ckv": pool if layer is not None else pool[0]}
        x = x + h
        h, _, pairs = _mlp(cfg, p["mlp"],
                           layers.apply_norm(cfg, p["mlp_norm"], x), dense)
        return x + h, state, pairs
    if kind == ATTN and pages is not None:
        h, state = attention.paged_decode_attention(cfg, p["attn"], h,
                                                    state, t, pages,
                                                    impl=attn_impl)
    elif kind in (ATTN, SWA, LOCAL):
        h, state = attention.decode_attention(cfg, p["attn"], h, state, t,
                                              kind, impl=attn_impl)
    elif kind == XATTN:
        # Cross K/V are precomputed once (prefill); just attend.
        q, _, _ = attention._project_qkv(cfg, p["attn"], h, h[:, :1])
        B = x.shape[0]
        Sk = state["k_mem"].shape[1]
        bias = jnp.zeros((B, 1, 1, Sk), jnp.float32)
        out = attention._sdpa_grouped(cfg, q, state["k_mem"].astype(q.dtype),
                                      state["v_mem"].astype(q.dtype), bias)
        out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
        h = layers.apply_linear(p["attn"]["wo"], out)
    elif kind == RGLRU:
        h, state = rglru.apply_rglru_block(cfg, p["rglru"], h, state)
    elif kind == MAMBA:
        h, state = ssm.apply_mamba_block(cfg, p["mamba"], h, state)
    x = x + h
    if kind != MAMBA:
        h = layers.apply_norm(cfg, p["mlp_norm"], x)
        if cfg.num_experts:
            h, _ = moe.apply_moe(cfg, p["mlp"], h)
        else:
            h = layers.apply_mlp(cfg, p["mlp"], h)
        x = x + h
    return x, state, None


def decode_step(cfg: ModelConfig, params: dict, state: dict,
                tokens: jax.Array, t: jax.Array, attn_impl: str = "auto",
                pages: Optional[jax.Array] = None,
                return_counts: bool = False):
    """One decode step. tokens [B,1] int32; t = absolute position — scalar
    (lockstep batch) or ``[B]`` vector (continuous batching / ragged rows,
    each cache row at its own position).

    ``attn_impl`` ("auto" | "dense" | "flash") picks the attention leaf
    for every ATTN/SWA/LOCAL block (see attention.decode_attention); it
    is static config resolved at trace time, so executable caches must
    key on it. With ``pages`` ([B, n_log] int32 page table), full-context
    ATTN layers read their state as a shared page pool (see
    ``decode_state_spec``'s paged mode) — the table is loop-invariant
    across the repeat scan, so it rides in by closure, not as a carry.
    Where every layer is latent attention (``paged_in_place``), each
    stacked pool instead rides the scan as a carry and is read and written
    in place, with the layer index as a scan input: no per-layer slice of
    the pool is copied.
    Returns (logits [B,1,V], new_state), and with ``return_counts`` the
    routed pairs on held experts per row, summed over layers ([B] int32).
    """
    x = layers.embed_tokens(cfg, params["embed"], tokens)
    x = shard(x, "dp", None, None)
    new_state: dict[str, Any] = {}
    counts = jnp.zeros((x.shape[0],), jnp.int32) if return_counts else None

    def add(counts, pairs):
        return counts if pairs is None or counts is None \
            else counts + pairs[:, 0]

    if "lead" in params:
        new_state["lead"] = {}
        for i, kind in enumerate(cfg.lead):
            x, new_state["lead"][str(i)], pr = _decode_block(
                cfg, kind, params["lead"][str(i)], x, state["lead"][str(i)],
                t, attn_impl, pages, dense=True)
            counts = add(counts, pr)

    if "blocks" in params and pages is not None and paged_in_place(cfg):
        def body(carry, inputs):
            h, blk_state, n = carry
            blk_params, li = inputs
            blk_state = dict(blk_state)
            for i, kind in enumerate(cfg.pattern):
                h, blk_state[str(i)], pr = _decode_block(
                    cfg, kind, blk_params[str(i)], h, blk_state[str(i)], t,
                    attn_impl, pages, layer=li)
                n = add(n, pr)
            return (h, blk_state, n), None
        (x, new_state["blocks"], counts), _ = _repeat_blocks(
            body, (x, state["blocks"], counts), params["blocks"],
            extra=jnp.arange(cfg.num_repeats, dtype=jnp.int32))
    elif "blocks" in params:
        def body(carry, inputs):
            h, n = carry
            blk_params, blk_state = inputs
            new_blk_state = {}
            for i, kind in enumerate(cfg.pattern):
                h, s, pr = _decode_block(cfg, kind, blk_params[str(i)], h,
                                         blk_state[str(i)], t, attn_impl,
                                         pages)
                new_blk_state[str(i)] = s
                n = add(n, pr)
            return (h, n), new_blk_state
        (x, counts), new_state["blocks"] = _repeat_blocks(
            body, (x, counts), params["blocks"], extra=state["blocks"])

    if "tail" in params:
        new_state["tail"] = {}
        for i, kind in enumerate(cfg.remainder):
            x, s, pr = _decode_block(cfg, kind, params["tail"][str(i)], x,
                                     state["tail"][str(i)], t, attn_impl,
                                     pages)
            new_state["tail"][str(i)] = s
            counts = add(counts, pr)

    x = layers.apply_norm(cfg, params["final_norm"], x)
    logits = layers.lm_logits(cfg, params["embed"], x)
    logits = shard(logits, "dp", None, "tp")
    if return_counts:
        return logits, new_state, counts
    return logits, new_state


# ---------------------------------------------------------------------------
# Chunked prefill: extend a decode state by a block of prompt tokens
# ---------------------------------------------------------------------------

def _extend_block(cfg: ModelConfig, kind: str, p: dict, x: jax.Array,
                  state: dict, t0: jax.Array, dense: bool = False):
    if kind not in (ATTN, SWA, LOCAL, MLA):
        raise ValueError(
            f"chunked prefill needs an attention-only stack, got {kind!r}")
    h = layers.apply_norm(cfg, p["norm"], x)
    if kind == MLA:
        h, state = mla.extend_attention(cfg, p["attn"], h, state, t0)
        x = x + h
        h, _, _ = _mlp(cfg, p["mlp"], layers.apply_norm(cfg, p["mlp_norm"], x),
                       dense)
        return x + h, state
    h, state = attention.extend_attention(cfg, p["attn"], h, state, t0, kind)
    x = x + h
    h = layers.apply_norm(cfg, p["mlp_norm"], x)
    if cfg.num_experts:
        h, _ = moe.apply_moe(cfg, p["mlp"], h)
    else:
        h = layers.apply_mlp(cfg, p["mlp"], h)
    x = x + h
    return x, state


def prefill_extend(cfg: ModelConfig, params: dict, state: dict,
                   tokens: jax.Array, t0: jax.Array):
    """Advance a decode state by a chunk of ``C`` prompt tokens.

    tokens [B,C] int32 at absolute positions ``t0 .. t0+C-1``; ``state``
    comes from ``init_decode_state`` (first chunk: positions mask every
    zeroed slot invalid) or a previous ``prefill_extend``. Attention-only
    stacks: recurrent blocks (RG-LRU / Mamba) would need their own chunk
    scan, and XATTN needs frontend memory — the engine gates those to the
    monolithic exact-length prefill.

    Returns (last-position logits [B,1,V], new_state positioned at
    ``t0 + C``) — feed the next chunk at ``t0 + C``, or sample the first
    generated token from the logits after the final chunk.
    """
    x = layers.embed_tokens(cfg, params["embed"], tokens)
    x = shard(x, "dp", None, None)
    new_state: dict[str, Any] = {}

    if "lead" in params:
        new_state["lead"] = {}
        for i, kind in enumerate(cfg.lead):
            x, new_state["lead"][str(i)] = _extend_block(
                cfg, kind, params["lead"][str(i)], x, state["lead"][str(i)],
                t0, dense=True)

    if "blocks" in params:
        def body(h, inputs):
            blk_params, blk_state = inputs
            new_blk_state = {}
            for i, kind in enumerate(cfg.pattern):
                h, s = _extend_block(cfg, kind, blk_params[str(i)], h,
                                     blk_state[str(i)], t0)
                new_blk_state[str(i)] = s
            return h, new_blk_state
        x, new_state["blocks"] = _repeat_blocks(
            body, x, params["blocks"], extra=state["blocks"])

    if "tail" in params:
        new_state["tail"] = {}
        for i, kind in enumerate(cfg.remainder):
            x, s = _extend_block(cfg, kind, params["tail"][str(i)], x,
                                 state["tail"][str(i)], t0)
            new_state["tail"][str(i)] = s

    x = layers.apply_norm(cfg, params["final_norm"], x[:, -1:])
    logits = layers.lm_logits(cfg, params["embed"], x)
    return shard(logits, "dp", None, "tp"), new_state
