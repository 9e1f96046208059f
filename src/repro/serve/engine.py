"""Continuous-batching decode engine over a slotted KV cache.

The lockstep serving path (drain a queue, pad a batch, run
prefill+decode, reply per batch) makes every request wait for a batch
boundary and the whole batch wait for its slowest member. This engine
replaces that with *iteration-level* scheduling:

  * the KV cache is a fixed pool of ``num_slots`` rows
    (``transformer.init_decode_state`` with batch = num_slots);
  * a persistent decode loop steps ALL occupied slots together, each at
    its own absolute position (``decode_step`` with a per-row ``t``
    vector — ring-position masking keeps ragged rows correct);
  * decode runs in *fused windows*: sampling and the per-row
    feed-token/position updates live inside one jitted ``lax.scan``
    (``serve.decode.make_fused_serve_step``), so the feed tokens, the
    position vector, and the PRNG key stay device-resident and the host
    syncs one ``[num_slots, K]`` token block per window instead of one
    token per step. ``sync_every`` caps K (default 8); each window's K
    is picked from the power-of-two ladder by useful-tokens-per-cost
    (see ``step``), so draining tails shrink the window instead of
    burning speculative steps and at most log2(sync_every)+1
    executables exist. EOS / ``max_new``
    retirement is detected on the sync by slicing each row's block to
    its own stop point — bit-identical to syncing every step, because
    the scan body IS the single-step path;
  * ``decode_impl`` picks the attention leaf ("auto" | "dense" |
    "flash"): flash routes through the ``kernels.ops`` dispatcher — the
    one-HBM-pass flash-decode kernel on TPU, its jnp oracle as a native
    XLA executable elsewhere — with the ring-validity mask handed to the
    kernel as its precomputed ``valid`` mask;
  * arrivals are admitted into free slots *between* windows: the
    request is prefilled alone at its exact prompt length and its
    per-layer state is written into the free row with
    ``transformer.write_decode_slot`` (a donated dynamic-update, so
    admission never copies or perturbs in-flight rows);
  * with ``prefill_chunk`` set, a long prompt prefills in fixed-size
    chunks interleaved between decode windows (``prefill_extend``
    against a reserved slot's own B=1 state), so a long prompt never
    stalls in-flight decode for its full prefill. Chunking needs an
    attention-only stack; other stacks (and short prompts) fall back to
    the monolithic exact-length prefill. Admission order stays strict
    FCFS: while a chunked prefill is in progress, later arrivals wait;
  * a sequence retires the moment it finishes (EOS or its ``max_new``
    budget) and its slot is immediately reusable — nobody waits for a
    batch-mate;
  * replies stream back per request through ``concurrent.futures``.

Exact-length prefill (no padding) keeps admission correct for every
``decode_supported`` architecture, including the recurrent ones
(RG-LRU / Mamba) whose state a padded prefill would pollute; jit caches
one prefill executable per distinct prompt length. Requests that cannot
ever fit (prompt + max_new > context_len) fail their own future at
submit time — they never poison a step, and the queue keeps serving
everyone else. A full pool queues requests (FCFS) instead of erroring.

Paged KV mode (``page_size`` set): full-context ATTN layers swap their
flat ``[num_slots, L]`` rings for a shared pool of ``num_pages``
fixed-size pages plus a host-resident ``[num_slots, n_log]`` page table
(mutated freely on admission/retirement, re-uploaded — a few hundred
bytes — once per fused window),
so a row's cache footprint is ``ceil((prompt+max_new)/page_size)``
pages instead of a full max-L ring and the concurrency limit is total
*pages*, not rows × max_L. Admission reserves a row's whole page budget
up front (deadlock-free: a decode window can never run out mid-flight —
"appending a page on a boundary crossing" is the pre-assigned page-table
entry coming live as ``t`` crosses it), retirement refcount-releases the
pages and re-points the row's table entries at the trash page (physical
page 0), where free rows' and speculative post-retirement writes land
harmlessly. On top of paging, a refcounted prefix cache
(``serve.paging.PrefixCache``) lets a prompt sharing a cached
page-aligned prefix skip that prefix's prefill: its leading page-table
entries alias the shared pages (copy-on-write by construction — shared
pages are fully prompt-covered, and decode writes start at the prompt
end) and only the suffix runs through ``prefill_extend``. Windowed
(SWA/local) rings and recurrent state stay per-row — already
footprint-bounded — which also scopes the prefix cache to causal
attention-only stacks.

MoE caveat: expert routing under a capacity factor couples rows through
the shared capacity budget, so MoE decode in a shared pool is not
bit-identical to serving the same request alone (dense / recurrent
stacks are).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import queue
import threading
import time
from concurrent import futures as cf
from typing import Any, Optional

import numpy as np

from repro.core import telemetry
from repro.models.config import ModelConfig

_CHUNKABLE_KINDS = {"attn", "swa", "local"}


def _pow2ceil(n: int) -> int:
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass
class _Request:
    prompt: np.ndarray            # [S] int32, detached copy
    max_new: int
    future: cf.Future
    submitted: float
    # Trace attribution, captured on the submitting (RPC handler) thread:
    # the engine thread has no contextvar state, so spans for this request
    # are recorded against this explicit context.
    ctx: Optional[telemetry.TraceContext] = None
    wall: float = 0.0             # submit wall-clock (TTFT / span anchors)


@dataclasses.dataclass
class _Slot:
    request: _Request
    t: int                        # absolute position of the next token fed
    generated: list


@dataclasses.dataclass
class _PendingPrefill:
    """A chunked prefill in flight: the request holds its reserved slot
    while its prompt streams through ``prefill_extend`` one chunk per
    engine step, against its own B=1 state."""
    request: _Request
    slot: int
    state: Any                    # B=1 decode state (chunk-extended)
    consumed: int                 # prompt tokens already prefilled
    start_page: int = 0           # leading shared prefix pages (paged mode)


class ServeEngine:
    """Continuous-batching serve engine.

    ``submit()`` is thread-safe and returns a ``concurrent.futures.Future``
    resolving to the full sequence (prompt + generated tokens, int32).
    Drive the engine either with ``start()`` (daemon decode loop — the
    serving deployment) or by calling ``step()`` directly from one thread
    (deterministic, used by tests and benchmarks). If a step raises in the
    daemon loop, every queued and in-flight request fails with that
    exception and the engine stops (``alive`` turns False).

    The engine lives on the device that holds ``params`` (when they sit
    on one device): the decode state and the device-resident feed
    tokens/positions are allocated there, the engine thread allocates
    there, and swapped-in weights are moved there.
    """

    def __init__(self, cfg: ModelConfig, params, *, num_slots: int = 8,
                 context_len: int = 64, max_new: int = 16,
                 eos_id: Optional[int] = None, temperature: float = 0.0,
                 seed: int = 0, sync_every: int = 8,
                 top_k: Optional[int] = None, decode_impl: str = "auto",
                 prefill_chunk: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 prefix_cache: bool = True):
        import jax
        import jax.numpy as jnp
        from repro.kernels import ops as kernel_ops
        from repro.models import transformer
        from repro.serve import decode as serve_lib

        if not cfg.decode_supported:
            raise ValueError(f"{cfg.name} has no autoregressive decode step")
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if sync_every < 1:
            raise ValueError("sync_every must be >= 1")
        if decode_impl not in ("auto", "dense", "flash"):
            raise ValueError(f"decode_impl must be auto|dense|flash, "
                             f"got {decode_impl!r}")
        if page_size is not None and page_size < 1:
            raise ValueError("page_size must be >= 1")
        if (page_size is not None and decode_impl != "dense"
                and jax.default_backend() == "tpu"
                and not kernel_ops.paged_page_size_ok(page_size,
                                                      cfg.head_dim)):
            raise ValueError(
                f"page_size {page_size} cannot be tiled by the paged "
                f"flash-decode kernel at head_dim {cfg.head_dim}: its "
                "double-buffered K/V page blocks overflow the kernel's "
                f"{kernel_ops.SCOPED_VMEM_BYTES >> 20} MiB VMEM budget; "
                "use a smaller page_size")
        leaf = jax.tree.leaves(params)[0]
        devices = leaf.devices() if isinstance(leaf, jax.Array) else set()
        self._device = next(iter(devices)) if len(devices) == 1 else None
        self._cfg = cfg
        self._params = params
        self._ns = num_slots
        self._L = context_len
        self._max_new = max_new
        self._eos = eos_id
        self._temp = temperature
        self._top_k = top_k
        self._impl = decode_impl
        self._sync = sync_every
        self._key = jax.random.key(seed) if temperature else None

        kinds = set(cfg.pattern) | set(cfg.remainder)
        # The internal ring modulus (_Lp) is context_len rounded UP: to
        # whole pages when paged, else to a length the flat flash-decode
        # kernel tiles without padding the cache on every call. submit()
        # still rejects prompt+max_new > context_len, so positions never
        # wrap for any modulus >= context_len and the ring-validity math
        # is unchanged.
        # Stacks with no full-context ATTN layer (pure windowed/recurrent)
        # have nothing to page — they accept the knobs but run the flat
        # per-row layout with an unlimited "pool".
        self._paged = page_size is not None
        if self._paged:
            self._ps = int(page_size)
            self._n_log = -(-context_len // self._ps)
            self._Lp = self._n_log * self._ps
            self._has_paged = "attn" in kinds
            self._P = (int(num_pages) if num_pages is not None
                       else num_slots * self._n_log)
            if self._has_paged and self._P < self._n_log:
                # Not fatal — short requests still fit — but a max-size
                # request can never be admitted; submit() rejects per-request.
                pass
        else:
            self._ps = 0
            self._Lp = (context_len if decode_impl == "dense"
                        else kernel_ops.decode_cache_len(context_len))
            self._has_paged = False
        # Compact windows: with every cache leaf behind the page table
        # (attention-only stack), the fused window's batch width is a free
        # choice — the executable sees [W] page-table rows, tokens and
        # positions, never the pool's row count — so windows run at the
        # ACTIVE row count (padded up to a compiled width) and idle slots
        # cost nothing. The flat ring cannot do this without physically
        # compacting KV rows, which is the structural reason extra paged
        # admission capacity is ~free. Stacks with per-row state leaves
        # (SWA rings, recurrent, conv) keep full-width windows.
        self._compact = self._has_paged and kinds == {"attn"}
        self._chunk = prefill_chunk
        self._can_chunk = (prefill_chunk is not None
                           and kinds <= _CHUNKABLE_KINDS
                           and not cfg.conv_pos)
        if prefill_chunk is not None:
            ring = min((min(self._Lp, cfg.window or self._Lp)
                        if k in ("swa", "local") else self._Lp)
                       for k in kinds)
            if not 1 <= prefill_chunk <= ring:
                raise ValueError(
                    f"prefill_chunk ({prefill_chunk}) must be in [1, "
                    f"{ring}] (the smallest cache ring) — a larger chunk "
                    "would overwrite slots its own queries still attend to")

        with self._on_device():
            self._state = self._init_state()
            # Device-resident hot state: the feed tokens and per-row
            # positions live on device between syncs (rebuilding them
            # from host numpy every step was a measurable per-step tax),
            # and the fused window threads them through donated buffers.
            self._tokens_dev = jnp.zeros((num_slots, 1), jnp.int32)
            self._t_dev = jnp.zeros((num_slots,), jnp.int32)
        if self._has_paged:
            # The page table lives on HOST (tiny int32 [ns, n_log]): it
            # mutates on every admission/retirement, and host writes are
            # free where device .at[] updates were one jit call each; the
            # fused window re-uploads the ~KB table once per window.
            self._pages_tab = np.zeros((num_slots, self._n_log), np.int32)
            self._free_pages: list[int] = list(range(self._P, 0, -1))
            self._page_rc: list[int] = [0] * (self._P + 1)
            self._row_pages: list[Optional[list[int]]] = [None] * num_slots
            self._ppr_ewma = 0.0            # pages per admitted request
            prefix_ok = (prefix_cache and cfg.causal and not cfg.conv_pos
                         and kinds <= {"attn"})
            if prefix_ok:
                from repro.serve.paging import PrefixCache
                self._prefix: Optional[PrefixCache] = PrefixCache(self._ps)
            else:
                self._prefix = None
        else:
            self._pages_tab = None
            self._prefix = None
        self._slots: list[Optional[_Slot]] = [None] * num_slots
        self._free: list[int] = list(range(num_slots - 1, -1, -1))

        # Fused-window executables, shared across engine instances via the
        # lru cache in serve.decode (keyed on every static knob, attn_impl
        # included — the kernel-vs-dense choice is baked at trace time).
        self._fused = functools.partial(
            serve_lib.cached_fused_step, cfg, temperature=temperature,
            top_k=top_k, attn_impl=decode_impl)
        self._sampler = jax.jit(serve_lib.make_sampler(temperature, top_k))

        Lp = self._Lp

        def _prefill_fn(params, tokens, key=None):
            logits, state = transformer.prefill(cfg, params, tokens=tokens,
                                                context_len=Lp)
            nxt = serve_lib.make_sampler(temperature, top_k)(
                logits[:, -1:], key)
            return nxt, state

        # One executable per distinct prompt length (jit's shape cache).
        self._prefill = jax.jit(_prefill_fn)
        self._extend = jax.jit(
            functools.partial(transformer.prefill_extend, cfg),
            donate_argnums=(1,))
        self._write = jax.jit(
            functools.partial(transformer.write_decode_slot, cfg),
            donate_argnums=(0,))
        if self._has_paged:
            ps = self._ps

            def _write_paged_fn(state, slot_state, i, row_pages,
                                start_page):
                return transformer.write_paged_slot(
                    cfg, state, slot_state, i, row_pages, start_page, ps)

            self._write_paged = jax.jit(_write_paged_fn,
                                        donate_argnums=(0,))
            self._gather = jax.jit(
                lambda state, i, row_pages: transformer.gather_paged_slot(
                    cfg, state, i, row_pages, ps))

        def _row_write_fn(tokens, t, i, tok, tval):
            return tokens.at[i, 0].set(tok), t.at[i].set(tval)

        self._row_write = jax.jit(_row_write_fn, donate_argnums=(0, 1))

        self._queue: queue.Queue[_Request] = queue.Queue()
        self._ready: collections.deque[_Request] = collections.deque()
        self._pending: Optional[_PendingPrefill] = None
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Set once the daemon loop serves (after its warm-up) or has exited.
        self.ready = threading.Event()
        self._closed = False
        self._lock = threading.Lock()                       # stats + lifecycle
        self._error: Optional[BaseException] = None   # why the loop died
        self._counters = dict(submitted=0, admitted=0, retired=0, failed=0,
                              steps=0, decode_tokens=0, generated_tokens=0,
                              occupancy_sum=0, peak_occupancy=0,
                              host_syncs=0, prefix_tokens_reused=0,
                              param_swaps=0)
        # Weight hot-swap handoff: (params, applied-event), installed by
        # the engine thread at the top of its next step.
        self._pending_swap: Optional[tuple[Any, threading.Event]] = None
        # Node attribution for spans recorded on the engine thread (which
        # never gets a WorkerContext); captured here, on the constructing
        # node's thread.
        self._node = telemetry.node_name()
        # EWMA decode-step microseconds per token: the routing signal a
        # load balancer uses to weigh this engine against its siblings.
        self._ewma_us_tok = 0.0

    def _on_device(self):
        """Context that allocates on this engine's device."""
        import contextlib
        import jax
        if self._device is None:
            return contextlib.nullcontext()
        return jax.default_device(self._device)

    def _init_state(self):
        """A zeroed full-width decode state: the page pool (P usable
        pages + the trash page, id 0) in paged mode, else flat rings."""
        from repro.models import transformer
        if self._has_paged:
            return transformer.init_decode_state(
                self._cfg, self._ns, self._Lp, page_size=self._ps,
                num_pages=self._P + 1)
        return transformer.init_decode_state(self._cfg, self._ns, self._Lp)

    # -- client side ---------------------------------------------------------
    def submit(self, prompt, max_new: Optional[int] = None) -> cf.Future:
        """Enqueue one request; resolves to [S + n_generated] int32.

        The prompt is copied (a transport-owned zero-copy view is safe to
        hand in; its lease is released as soon as submit returns). A
        request that cannot fit the slot ring fails its own future here —
        per-request delivery, no effect on its neighbours.
        """
        fut: cf.Future = cf.Future()
        prompt = np.asarray(prompt, np.int32).reshape(-1).copy()
        mn = self._max_new if max_new is None else int(max_new)
        if prompt.size == 0:
            fut.set_exception(ValueError("empty prompt"))
            return fut
        if prompt.size + mn > self._L:
            fut.set_exception(ValueError(
                f"prompt ({prompt.size}) + max_new ({mn}) exceeds the "
                f"engine's context_len ({self._L})"))
            return fut
        if self._has_paged and self._page_need(prompt.size, mn) > self._P:
            fut.set_exception(ValueError(
                f"request needs {self._page_need(prompt.size, mn)} KV "
                f"pages; the pool only has {self._P}"))
            return fut
        with self._lock:
            # The put happens under the same lock stop() takes before
            # draining, so a request can never slip into the queue after
            # the drain and hang its caller.
            if self._closed:
                fut.set_exception(self._error
                                  or RuntimeError("engine stopped"))
                return fut
            self._counters["submitted"] += 1
            ctx = telemetry.current_context()
            self._queue.put(_Request(
                prompt, mn, fut, time.monotonic(),
                ctx=ctx if ctx is not None and ctx.sampled else None,
                wall=time.time()))
        self._wake.set()
        return fut

    def swap_params(self, params, block: bool = True,
                    timeout_s: float = 60.0) -> None:
        """Hot-swap the model weights (a zero-downtime rollout's engine
        half). The new tree is installed by the engine thread *between*
        decode windows — admission and decode both see a consistent tree
        for any one window, never a mix. Because params are a per-call
        operand to every compiled executable, a shape/dtype-identical
        swap reuses the entire warmed ladder: no recompile, no re-warm
        cost (``EngineServer.load_version`` enforces shape identity by
        restoring against the current tree).

        With ``block=True`` (and a running engine thread) waits until the
        swap has been applied. When the engine is driven by external
        ``step()`` calls, the swap lands on the caller's next step.
        """
        done = threading.Event()
        with self._lock:
            if self._closed:
                raise RuntimeError("engine stopped")
            prev = self._pending_swap
            self._pending_swap = (params, done)
        if prev is not None:
            prev[1].set()       # superseded before it was applied
        self._wake.set()
        if block and self._thread is not None:
            if not done.wait(timeout_s):
                raise TimeoutError("param swap not applied within "
                                   f"{timeout_s}s")

    def _apply_pending_swap(self) -> None:
        with self._lock:
            swap, self._pending_swap = self._pending_swap, None
        if swap is None:
            return
        params, done = swap
        if self._device is not None:
            import jax
            params = jax.device_put(params, self._device)
        self._params = params
        with self._lock:
            self._counters["param_swaps"] += 1
        done.set()

    # -- page accounting (paged mode, engine thread only) --------------------
    def _page_need(self, prompt_len: int, max_new: int) -> int:
        total = min(prompt_len + max_new, self._Lp)
        return -(-total // self._ps)

    def _incref(self, pid: int) -> None:
        self._page_rc[pid] += 1

    def _decref(self, pid: int) -> None:
        self._page_rc[pid] -= 1
        if self._page_rc[pid] == 0:
            self._free_pages.append(pid)

    def _alloc_pages(self, n: int) -> Optional[list[int]]:
        """Take ``n`` pages off the free list (each born with refcount 1),
        evicting refcount-zero-able prefix-cache entries LRU-first under
        pressure. None = the pool genuinely cannot satisfy ``n`` right now
        (admission blocks FCFS until retirements release pages)."""
        while len(self._free_pages) < n:
            if self._prefix is None or not self._prefix.evict_one(self._decref):
                return None
        out = [self._free_pages.pop() for _ in range(n)]
        for pid in out:
            self._page_rc[pid] = 1
        return out

    def _release_pages(self, pages: Optional[list[int]]) -> None:
        if pages:
            for pid in pages:
                self._decref(pid)

    def _pages_arr(self, row_pages: list[int]):
        """Row page list padded to the full logical length with trash-page
        entries (speculative writes past the reservation land there).
        Host numpy: it feeds both the host page table and jit operands
        (converted at the call boundary)."""
        pad = [0] * (self._n_log - len(row_pages))
        return np.asarray(row_pages + pad, np.int32)

    def _register_prefix(self, prompt: np.ndarray,
                         row_pages: list[int]) -> None:
        if self._prefix is not None:
            self._prefix.insert(prompt, row_pages, self._incref,
                                self._decref)

    def _window_width(self, n: int) -> int:
        """Compact-window batch width for ``n`` active rows: the smallest
        power-of-two >= n, capped at ``num_slots`` — so at most
        log2(num_slots)+2 width shapes ever compile per window length."""
        w = 1
        while w < n and w < self._ns:
            w *= 2
        return min(w, self._ns)

    # -- engine side ---------------------------------------------------------
    def _activate(self, req: _Request, i: int, first: int,
                  path: str = "direct") -> None:
        """Mark slot ``i`` live: host bookkeeping + the device-resident
        feed-token/position rows (one donated row write, no full-array
        host->device rebuild). Compact-window engines skip the device
        write: their windows rebuild the [W] feed operands from host slot
        state anyway, so the per-admission jit call would be pure tax.

        The first generated token exists here, so this is where
        time-to-first-token lands — classed by prefill path (``direct``
        vs ``chunked``), the two populations whose TTFT distributions an
        SLO policy must not average together."""
        import jax.numpy as jnp
        self._slots[i] = _Slot(request=req, t=len(req.prompt),
                               generated=[first])
        if req.wall:
            telemetry.metrics().histogram(
                f"engine.ttft_us.{path}").record(
                    (time.time() - req.wall) * 1e6)
        if not self._compact:
            self._tokens_dev, self._t_dev = self._row_write(
                self._tokens_dev, self._t_dev, jnp.int32(i), jnp.int32(first),
                jnp.int32(len(req.prompt)))
        with self._lock:
            self._counters["admitted"] += 1
            self._counters["host_syncs"] += 1   # the first-token pull
        if (self._eos is not None and first == self._eos) \
                or req.max_new <= 1:
            self._retire(i)

    def _admit(self) -> None:
        """Move queued requests into free slots: exact-length prefill, then
        write the fresh per-layer state into the slot's cache row. Long
        prompts (with ``prefill_chunk`` on an attention-only stack) are
        parked as a _PendingPrefill instead and stream through
        ``_advance_chunk`` one chunk per step; admission order stays
        strict FCFS, so later arrivals wait behind an in-flight chunked
        prefill rather than jumping it.

        Paged mode reserves the row's whole page budget here (shared
        prefix pages + freshly allocated owned pages); a pool that cannot
        satisfy the head request blocks admission (FCFS) until
        retirements — or prefix-cache eviction — free pages. On a prefix
        hit the shared pages are gathered into a flat B=1 view and only
        the prompt *suffix* runs through ``prefill_extend``; the
        copy-on-write scatter then lands just the owned pages."""
        import jax.numpy as jnp
        while True:
            try:
                self._ready.append(self._queue.get_nowait())
            except queue.Empty:
                break
        while self._free and self._ready:
            req = self._ready[0]
            chunked = self._can_chunk and len(req.prompt) > self._chunk
            if chunked and self._pending is not None:
                return                          # FCFS: wait for the pending
            shared: list[int] = []
            row_pages: Optional[list[int]] = None
            if self._has_paged:
                n_need = self._page_need(len(req.prompt), req.max_new)
                if self._prefix is not None:
                    shared = self._prefix.lookup(req.prompt)
                owned = self._alloc_pages(n_need - len(shared))
                if owned is None:
                    return      # pool exhausted: FCFS-block at the head
                for pid in shared:
                    self._incref(pid)
                row_pages = shared + owned
            self._ready.popleft()
            if not req.future.set_running_or_notify_cancel():
                self._release_pages(row_pages)
                continue                                    # cancelled
            i = self._free.pop()
            if req.ctx is not None:
                # Admission wait: submit RPC -> a free slot (and, in paged
                # mode, the page budget) became this request's.
                telemetry.record_span("admission", req.ctx, req.wall,
                                      time.time() - req.wall,
                                      node=self._node, slot=i)
            c = len(shared)
            if self._has_paged:
                self._row_pages[i] = row_pages
                self._ppr_ewma = (float(len(row_pages))
                                  if self._ppr_ewma == 0.0 else
                                  0.2 * len(row_pages) + 0.8 * self._ppr_ewma)
                if c:
                    with self._lock:
                        self._counters["prefix_tokens_reused"] += c * self._ps
            if chunked:
                from repro.models import transformer
                if c:
                    state = self._gather(self._state, jnp.int32(i),
                                         self._pages_arr(row_pages))
                else:
                    state = transformer.init_decode_state(self._cfg, 1,
                                                          self._Lp)
                self._pending = _PendingPrefill(
                    request=req, slot=i, state=state,
                    consumed=c * self._ps, start_page=c)
                continue
            try:
                t0w, t0 = time.time(), time.perf_counter()
                key = self._split_key()
                if c:
                    flat = self._gather(self._state, jnp.int32(i),
                                        self._pages_arr(row_pages))
                    logits, slot_state = self._extend(
                        self._params, flat,
                        jnp.asarray(req.prompt[None, c * self._ps:]),
                        jnp.int32(c * self._ps))
                    nxt = self._sampler(logits, key)
                else:
                    nxt, slot_state = self._prefill(
                        self._params, jnp.asarray(req.prompt[None]), key)
                if self._has_paged:
                    arr = self._pages_arr(row_pages)
                    self._state = self._write_paged(
                        self._state, slot_state, jnp.int32(i), arr,
                        jnp.int32(c))
                    self._pages_tab[i] = arr
                    self._register_prefix(req.prompt, row_pages)
                else:
                    self._state = self._write(self._state, slot_state,
                                              jnp.int32(i))
                first = int(np.asarray(nxt)[0, 0])
                if req.ctx is not None:
                    telemetry.record_span(
                        "prefill", req.ctx, t0w,
                        time.perf_counter() - t0, node=self._node,
                        path="direct",
                        tokens=len(req.prompt) - c * self._ps)
            except Exception as exc:                        # noqa: BLE001
                # Per-request failure delivery: the slot goes straight back
                # and the step proceeds for everyone else.
                self._free.append(i)
                if self._has_paged:
                    self._release_pages(self._row_pages[i])
                    self._row_pages[i] = None
                    self._pages_tab[i] = 0
                with self._lock:
                    self._counters["failed"] += 1
                req.future.set_exception(exc)
                continue
            self._activate(req, i, first, path="direct")

    def _advance_chunk(self) -> bool:
        """Run ONE prefill chunk of the pending request (if any) between
        decode windows. The final chunk's logits seed the first generated
        token, and only then does the accumulated B=1 state land in the
        reserved slot row. Returns True if a chunk ran."""
        import jax.numpy as jnp
        p = self._pending
        if p is None:
            return False
        prompt = p.request.prompt
        c0 = p.consumed
        c1 = min(c0 + self._chunk, len(prompt))
        t0w, t0 = time.time(), time.perf_counter()
        try:
            toks = jnp.asarray(prompt[None, c0:c1])
            logits, p.state = self._extend(self._params, p.state, toks,
                                           jnp.int32(c0))
            p.consumed = c1
            if p.request.ctx is not None:
                telemetry.record_span("prefill", p.request.ctx, t0w,
                                      time.perf_counter() - t0,
                                      node=self._node, path="chunked",
                                      tokens=c1 - c0)
            if c1 < len(prompt):
                return True
            nxt = self._sampler(logits, self._split_key())
            first = int(np.asarray(nxt)[0, 0])
            if self._has_paged:
                rp = self._row_pages[p.slot]
                arr = self._pages_arr(rp)
                self._state = self._write_paged(
                    self._state, p.state, jnp.int32(p.slot), arr,
                    jnp.int32(p.start_page))
                self._pages_tab[p.slot] = arr
                self._register_prefix(p.request.prompt, rp)
            else:
                self._state = self._write(self._state, p.state,
                                          jnp.int32(p.slot))
        except Exception as exc:                            # noqa: BLE001
            self._free.append(p.slot)
            if self._has_paged:
                self._release_pages(self._row_pages[p.slot])
                self._row_pages[p.slot] = None
                self._pages_tab[p.slot] = 0
            self._pending = None
            with self._lock:
                self._counters["failed"] += 1
            p.request.future.set_exception(exc)
            return True
        self._pending = None
        self._activate(p.request, p.slot, first, path="chunked")
        return True

    def _split_key(self):
        if self._key is None:
            return None
        import jax
        self._key, sub = jax.random.split(self._key)
        return sub

    def step(self) -> int:
        """One engine iteration: advance a pending chunked prefill, admit
        arrivals, then decode every occupied slot one fused window.
        Returns the number of slots that decoded (0 = idle). Call from a
        single driver thread only.

        Chunk admission is budgeted at one chunk per decode step — up to
        ``sync_every`` chunks per engine iteration, since the fused
        window below covers that many steps. Advancing only one chunk
        per *window* would stretch a chunked prompt's admission (and,
        under strict FCFS, everyone queued behind it) by the window
        length."""
        self._apply_pending_swap()      # between windows, before admission
        progressed = False
        for _ in range(self._sync):
            progressed |= self._advance_chunk()
            self._admit()
            if self._pending is None:
                break
        active = [i for i, s in enumerate(self._slots) if s is not None]
        if not active:
            return 1 if progressed else 0
        # Window length: picked per window from the power-of-two ladder up
        # to sync_every (so at most log2(sync_every)+1 executables exist)
        # by scoring useful tokens per unit cost. A window costs ~K decode
        # steps plus ~one step of sync/dispatch overhead, and a row only
        # uses min(K, its remaining budget) of it — tokens past a row's
        # retirement are speculative waste. Maximizing
        # sum(min(K, rem)) / (K + 1) batches syncs when budgets are deep
        # and shrinks the window when most rows are about to retire,
        # instead of burning a full window on a draining tail.
        rems = [s.request.max_new - len(s.generated)
                for s in self._slots if s is not None]
        k_eff, best, k = 1, -1.0, 1
        while k <= self._sync:
            score = sum(min(k, r) for r in rems) / (k + 1)
            if score > best:
                best, k_eff = score, k
            k = min(k * 2, self._sync) if k < self._sync else k * 2
        t0w = time.time()
        t0 = time.perf_counter()
        row_of = None
        if self._compact:
            # Window width = active count padded up to a compiled ladder
            # width: the executable reads [W] page-table rows / feed
            # tokens / positions, never the slot count, so idle capacity
            # rows cost zero compute. Pad rows carry an all-trash table
            # and t=0 (all-invalid attention -> zeros); their writes land
            # in the trash page. The feed operands rebuild from host slot
            # state — a few dozen bytes per window.
            W = self._window_width(len(active))
            toks_w = np.zeros((W, 1), np.int32)
            t_w = np.zeros((W,), np.int32)
            pages_w = np.zeros((W, self._n_log), np.int32)
            for w, i in enumerate(active):
                s = self._slots[i]
                toks_w[w, 0] = s.generated[-1]
                t_w[w] = s.t
                pages_w[w] = self._pages_tab[i]
            toks, self._state, _, _, key = \
                self._fused(k_eff)(self._params, self._state, toks_w, t_w,
                                   self._key, pages_w)
            row_of = {i: w for w, i in enumerate(active)}
        elif self._has_paged:
            toks, self._state, self._tokens_dev, self._t_dev, key = \
                self._fused(k_eff)(self._params, self._state,
                                   self._tokens_dev, self._t_dev, self._key,
                                   self._pages_tab)
        else:
            toks, self._state, self._tokens_dev, self._t_dev, key = \
                self._fused(k_eff)(self._params, self._state,
                                   self._tokens_dev, self._t_dev, self._key)
        if self._key is not None:
            self._key = key
        toks = np.asarray(toks)           # ONE host sync per K-token window
        win_dur = time.perf_counter() - t0
        us_tok = win_dur * 1e6 / (len(active) * k_eff)
        # Each sampled in-flight request gets this window as a span — the
        # loop is a no-op (ctx is None) unless a trace is actually live.
        for i in active:
            rq = self._slots[i].request
            if rq.ctx is not None:
                telemetry.record_span("decode", rq.ctx, t0w, win_dur,
                                      node=self._node, k=k_eff,
                                      active=len(active))
        with self._lock:
            c = self._counters
            c["steps"] += k_eff
            c["decode_tokens"] += len(active) * k_eff
            c["occupancy_sum"] += len(active) * k_eff
            c["peak_occupancy"] = max(c["peak_occupancy"], len(active))
            c["host_syncs"] += 1
            self._ewma_us_tok = us_tok if self._ewma_us_tok == 0.0 \
                else 0.2 * us_tok + 0.8 * self._ewma_us_tok
        for i in active:
            slot = self._slots[i]
            # Slice this row's block to its own stop point: tokens past EOS
            # or the max_new budget were computed speculatively inside the
            # window and are simply dropped (the ring rows they touched are
            # rewritten on the slot's next admission).
            for j in range(k_eff):
                tok = int(toks[row_of[i] if row_of is not None else i, j])
                slot.generated.append(tok)
                slot.t += 1
                if (self._eos is not None and tok == self._eos) \
                        or len(slot.generated) >= slot.request.max_new:
                    self._retire(i)
                    break
        return len(active)

    def _retire(self, i: int) -> None:
        slot = self._slots[i]
        self._slots[i] = None
        self._free.append(i)
        if self._has_paged and self._row_pages[i] is not None:
            # Release the refs and re-point the row at the trash page: the
            # freed row keeps riding the fused window until reused, and its
            # speculative writes must not corrupt reallocated pages. The
            # table is host numpy, so this is a free write, not a jit call.
            self._release_pages(self._row_pages[i])
            self._row_pages[i] = None
            self._pages_tab[i] = 0
        out = np.concatenate([slot.request.prompt,
                              np.asarray(slot.generated, np.int32)])
        with self._lock:
            self._counters["retired"] += 1
            self._counters["generated_tokens"] += len(slot.generated)
        slot.request.future.set_result(out)

    # -- lifecycle -----------------------------------------------------------
    def warmup(self) -> "ServeEngine":
        """Compile every fused-window executable this engine can select
        (the power-of-two K ladder up to ``sync_every`` — the *paged*
        ladder when paging is on, with the page table threaded as a real
        operand) plus, with ``prefill_chunk`` set, the chunk-shaped
        ``prefill_extend`` executable, all against throwaway state, so
        nothing compiles mid-serving. Prompt-length prefill shapes still
        compile on first sight — warm those by submitting representative
        prompts."""
        with self._on_device():
            self._warmup()
        return self

    def _warmup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.models import transformer
        state = self._init_state()
        if self._has_paged:
            # Compact engines pick a window width per window (the
            # power-of-two ladder up to num_slots), so warm the whole
            # width x K grid — a mid-run width change must not stall
            # serving on a compile.
            widths = []
            if self._compact:
                w = 1
                while w < self._ns:
                    widths.append(w)
                    w *= 2
            widths.append(self._ns)
            for width in widths:
                pages = jnp.zeros((width, self._n_log), jnp.int32)
                k = 1
                while k <= self._sync:
                    toks = jnp.zeros((width, 1), jnp.int32)
                    t = jnp.zeros((width,), jnp.int32)
                    key = None if self._key is None else jax.random.key(0)
                    out = self._fused(k)(self._params, state, toks, t, key,
                                         pages)
                    state = out[1]
                    jax.block_until_ready(out)
                    k = min(k * 2, self._sync) if k < self._sync else k * 2
        else:
            toks = jnp.zeros((self._ns, 1), jnp.int32)
            t = jnp.zeros((self._ns,), jnp.int32)
            key = None if self._key is None else jax.random.key(0)
            k = 1
            while k <= self._sync:
                out = self._fused(k)(self._params, state, toks, t, key)
                _, state, toks, t, key = out
                jax.block_until_ready(out)
                k = min(k * 2, self._sync) if k < self._sync else k * 2
        if self._can_chunk:
            st1 = transformer.init_decode_state(self._cfg, 1, self._Lp)
            chunk = jnp.zeros((1, self._chunk), jnp.int32)
            logits, _ = self._extend(self._params, st1, chunk, jnp.int32(0))
            jax.block_until_ready(logits)

    def start(self, warmup: bool = False) -> "ServeEngine":
        """Start the daemon decode loop. With ``warmup`` the loop thread
        first compiles the decode ladder (``warmup()``; the seconds land
        in the ``engine.warmup_s`` histogram) while submitted requests
        queue. ``ready`` is set once the loop serves."""
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop,
                                            args=(warmup,), daemon=True,
                                            name="serve-engine")
            self._thread.start()
        return self

    def _loop(self, warmup: bool = False) -> None:
        with self._on_device():
            try:
                if warmup:
                    t0 = time.perf_counter()
                    self._warmup()
                    telemetry.metrics().histogram("engine.warmup_s").record(
                        time.perf_counter() - t0)
                self.ready.set()
                while not self._stop.is_set():
                    if self.step() == 0:
                        self._wake.wait(timeout=0.05)
                        self._wake.clear()
            except Exception as exc:                        # noqa: BLE001
                # A warm-up or step that raises (a compile error, a device
                # fault) leaves the slot state unknown: stop serving and
                # hand the real error to every waiting caller now, instead
                # of letting them run out their timeouts.
                telemetry.get_logger(self._node).exception(
                    "engine step failed; engine stopped", error=repr(exc))
                with self._lock:
                    self._closed = True
                    self._error = exc
                self._fail_all(exc)
            finally:
                self.ready.set()

    def stop(self) -> None:
        """Stop the loop and fail anything still queued or in flight."""
        with self._lock:
            self._closed = True
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        self._fail_all(RuntimeError("engine stopped"))

    def _fail_all(self, err: BaseException) -> None:
        """Fail every queued, pending and in-flight request with ``err``
        and release their slots and pages (the engine is closed)."""
        with self._lock:
            swap, self._pending_swap = self._pending_swap, None
        if swap is not None:
            swap[1].set()       # unblock a swap_params caller mid-stop
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(err)
        while self._ready:
            req = self._ready.popleft()
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(err)
        if self._pending is not None:
            p, self._pending = self._pending, None
            self._free.append(p.slot)
            if self._has_paged:
                self._release_pages(self._row_pages[p.slot])
                self._row_pages[p.slot] = None
            p.request.future.set_exception(err)
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._slots[i] = None
                self._free.append(i)
                if self._has_paged:
                    self._release_pages(self._row_pages[i])
                    self._row_pages[i] = None
                slot.request.future.set_exception(err)
        if self._prefix is not None:
            self._prefix.clear(self._decref)

    def __enter__(self) -> "ServeEngine":
        return self.start()

    def __exit__(self, *exc_info) -> None:
        self.stop()

    # -- introspection -------------------------------------------------------
    @property
    def alive(self) -> bool:
        """False once the engine has been stopped (or killed): new
        submits and swaps fail — the health signal a serving wrapper
        should report upward."""
        with self._lock:
            return not self._closed

    @property
    def num_slots(self) -> int:
        return self._ns

    @property
    def context_len(self) -> int:
        return self._L

    def reset_stats(self) -> None:
        """Zero the counters (benchmarks: exclude warmup/compile from the
        measured window while keeping the warmed jit caches)."""
        with self._lock:
            for k in self._counters:
                self._counters[k] = 0

    def stats(self) -> dict:
        """Counters + derived occupancy; safe from any thread."""
        with self._lock:
            s = dict(self._counters)
            s["ewma_us_per_token"] = self._ewma_us_tok
        s["num_slots"] = self._ns
        s["free_slots"] = len(self._free)
        s["queue_depth"] = self._queue.qsize() + len(self._ready)
        s["mean_occupancy"] = (s["occupancy_sum"] / s["steps"]
                               if s["steps"] else 0.0)
        s["syncs_per_token"] = (s["host_syncs"] / s["generated_tokens"]
                                if s["generated_tokens"] else 0.0)
        if self._has_paged:
            s["pages_total"] = self._P
            s["pages_free"] = len(self._free_pages)
            s["pages_in_use"] = self._P - len(self._free_pages)
            s["pages_per_request_ewma"] = self._ppr_ewma
            if self._prefix is not None:
                s["prefix_cache"] = self._prefix.stats()
        return s

    def load(self) -> dict:
        """Cheap load report (the routing signal a fabric router uses):
        free KV slots, queued requests, EWMA decode us/token and — in
        paged mode — free pages / expected pages-per-request, so a router
        can score admission headroom in *pages* rather than rows. Safe
        from any thread, no full counter copy."""
        with self._lock:
            ewma = self._ewma_us_tok
            free = len(self._free)
        out = {"num_slots": self._ns, "free_slots": free,
               "queue_depth": self._queue.qsize() + len(self._ready),
               "ewma_us_per_token": ewma}
        if self._has_paged:
            out["pages_total"] = self._P
            out["free_pages"] = len(self._free_pages)
            out["pages_per_request_ewma"] = self._ppr_ewma
            out["prefix_hit_rate"] = (self._prefix.stats()["hit_rate"]
                                      if self._prefix is not None else 0.0)
        return out
