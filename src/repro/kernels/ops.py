"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to auto: real kernels on TPU, interpret-mode
execution on other backends (interpret mode runs the kernel body op by op
for correctness validation).

``decode_attention`` is the one wrapper on a serving hot path (the engine
calls it every token), so it carries a backend-aware dispatch table
instead of a bare jit: the Pallas kernel on TPU, the pure-jnp oracle as a
real XLA executable everywhere else. See README.md in this directory.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import mla_decode as _mla
from repro.kernels import ref as _ref
from repro.kernels import rglru_scan as _rg
from repro.kernels import ssm_scan as _ssm


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    interpret = _auto_interpret() if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)


LANE = 128      # TPU vector lane width: minor block dims tile by this


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _block_l(L: int, want: int) -> int:
    """Flat-kernel block along the cache length: the whole length when it
    fits one block (a block equal to the array dim always tiles), else the
    lane-aligned block that covers L in ``ceil(L / want)`` blocks with the
    least padding."""
    nblk = _cdiv(L, want)
    if nblk == 1:
        return L
    return _cdiv(_cdiv(L, nblk), LANE) * LANE


DECODE_BLOCK_L = 512    # decode_attention's default block along the cache


def decode_cache_len(L: int, block_l: int = DECODE_BLOCK_L) -> int:
    """The smallest cache length >= ``L`` that the flat kernel tiles
    without padding. A cache allocated at this length (slots past the
    context are never valid) saves ``decode_attention`` a padded copy of
    K and V on every call."""
    bl = _block_l(L, block_l)
    return _cdiv(L, bl) * bl


# Mosaic's default per-kernel VMEM budget on TPU v5e.
SCOPED_VMEM_BYTES = 16 * 2 ** 20


def paged_page_size_ok(page_size: int, head_dim: int) -> bool:
    """Whether the paged kernel can tile ``page_size`` on TPU. One grid
    step holds a ``(page_size, head_dim)`` bf16 K block and V block plus
    the page's ``(1, page_size)`` int32 mask block (padded to 8 sublanes),
    all double-buffered; they must fit the kernel's VMEM budget."""
    per_step = 2 * page_size * head_dim * 2 + 8 * page_size * 4
    return 2 * per_step <= SCOPED_VMEM_BYTES


def resolve_decode_impl(impl: Optional[str] = None,
                        interpret: Optional[bool] = None) -> str:
    """Dispatch rule for ``decode_attention``.

    Explicit ``impl`` wins (tests pin a path). Otherwise an explicit
    ``interpret`` flag selects the Pallas body (that flag only means
    something to the kernel), and the default is backend-driven: the real
    kernel on TPU, the jnp oracle — a fast native XLA executable, not
    Python interpret mode — elsewhere.

    NOTE: when called inside a traced function the choice is baked into
    that executable at trace time (the backend is host state); the serve
    engine keys its executable caches on the impl for this reason.
    """
    if impl is not None:
        if impl not in ("pallas", "ref"):
            raise ValueError(f"impl must be 'pallas' or 'ref', got {impl!r}")
        return impl
    if interpret is not None:
        return "pallas"
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@functools.partial(jax.jit, static_argnames=("block_l", "interpret"))
def _decode_pallas(q, k, v, valid, block_l: int, interpret: bool):
    return _dec.decode_attention(q, k, v, valid, block_l=block_l,
                                 interpret=interpret)


_decode_ref = jax.jit(_ref.decode_attention)


def decode_attention(q, k, v, valid, block_l: int = DECODE_BLOCK_L,
                     interpret: Optional[bool] = None,
                     impl: Optional[str] = None):
    """Backend-dispatched single-token attention (see resolve_decode_impl).

    q [B,H,dh]; k/v [B,L,KV,dh]; valid [B,L] bool -> [B,H,dh]. Both paths
    share one contract, including all-invalid rows -> zeros. Any ``L`` is
    accepted: the kernel path pads the cache to a whole number of
    lane-aligned blocks (pad slots are invalid, so they never count).
    That pad copies K and V, so callers on a hot path allocate their
    cache at ``decode_cache_len(L)`` (the serve engine does).
    """
    if resolve_decode_impl(impl, interpret) == "ref":
        return _decode_ref(q, k, v, valid)
    interpret = _auto_interpret() if interpret is None else interpret
    L = k.shape[1]
    bl = _block_l(L, block_l)
    pad = -L % bl
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        k, v = jnp.pad(k, widths), jnp.pad(v, widths)
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    return _decode_pallas(q, k, v, valid, bl, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_decode_pallas(q, k_pages, v_pages, pages, valid, interpret: bool):
    return _dec.paged_decode_attention(q, k_pages, v_pages, pages, valid,
                                       interpret=interpret)


_paged_decode_ref = jax.jit(_ref.paged_decode_attention)


def paged_decode_attention(q, k_pages, v_pages, pages, valid,
                           interpret: Optional[bool] = None,
                           impl: Optional[str] = None):
    """Backend-dispatched paged decode attention (same rule as the flat
    path — resolve_decode_impl — so CPU CI exercises the identical
    dispatch wiring with the jnp oracle as the leaf).

    q [B,H,dh]; k/v pages [P,ps,KV,dh]; pages [B,n] int32; valid [B,n*ps]
    -> [B,H,dh]. The block size is the page itself: the kernel walks the
    page list one physical page per grid step via scalar prefetch.
    """
    if resolve_decode_impl(impl, interpret) == "ref":
        return _paged_decode_ref(q, k_pages, v_pages, pages, valid)
    interpret = _auto_interpret() if interpret is None else interpret
    return _paged_decode_pallas(q, k_pages, v_pages, pages, valid, interpret)


MLA_PAGES_PER_BLOCK = 16     # pages the MLA kernel gathers per block


@functools.partial(jax.jit, static_argnames=("value", "sm_scale",
                                             "pages_per_block", "interpret"))
def _mla_decode_pallas(q, pool, layer, pages, lengths, value: int,
                       sm_scale: float, pages_per_block: int,
                       interpret: bool):
    return _mla.mla_decode(q, pool, layer, pages, lengths, value=value,
                           sm_scale=sm_scale,
                           pages_per_block=pages_per_block,
                           interpret=interpret)


_mla_decode_ref = jax.jit(_ref.mla_decode,
                          static_argnames=("value", "sm_scale"))


def mla_decode(q, pool, layer, pages, lengths, *, value: int,
               sm_scale: float, interpret: Optional[bool] = None,
               impl: Optional[str] = None):
    """Backend-dispatched paged latent-attention decode (the rule of
    ``resolve_decode_impl``). q [B,H,W]; pool [Lyr,P,ps,W] read in place;
    layer int32 scalar; pages [B,n]; lengths [B] -> [B,H,value]. The
    kernel path pads the page table to whole blocks with the trash page,
    a few hundred bytes per call."""
    if resolve_decode_impl(impl, interpret) == "ref":
        return _mla_decode_ref(q, pool, layer, pages, lengths, value=value,
                               sm_scale=sm_scale)
    interpret = _auto_interpret() if interpret is None else interpret
    ppb = min(MLA_PAGES_PER_BLOCK, pages.shape[1])
    pad = -pages.shape[1] % ppb
    if pad:
        pages = jnp.pad(pages, ((0, 0), (0, pad)))
    return _mla_decode_pallas(q, pool, layer, pages, lengths, value,
                              sm_scale, ppb, interpret)


@functools.partial(jax.jit, static_argnames=("block_s", "block_w",
                                             "interpret"))
def rglru_scan(a, x, h0, block_s: int = 256, block_w: int = 128,
               interpret: Optional[bool] = None):
    interpret = _auto_interpret() if interpret is None else interpret
    return _rg.rglru_scan(a, x, h0, block_s=block_s, block_w=block_w,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_s", "block_d",
                                             "interpret"))
def ssm_scan(u, delta, A, B, C, D, h0, block_s: int = 128,
             block_d: int = 128, interpret: Optional[bool] = None):
    interpret = _auto_interpret() if interpret is None else interpret
    return _ssm.ssm_scan(u, delta, A, B, C, D, h0, block_s=block_s,
                         block_d=block_d, interpret=interpret)
