"""LM serving as a Launchpad program — continuous batching by default.

Single-engine topology (``--replicas 1 --routers 0``, the PR-4 path):

    frontend clients (CourierNode × N)
      -> batcher (CourierNode: thin admission queue, per-request replies)
      -> model server (MeshWorkerNode: ServeEngine over a slotted KV cache)

Replicated serve fabric (``--replicas N --routers M``, M >= 1):

    frontend clients (CourierNode × N)
      -> routers (CourierNode × M: least-loaded dispatch, failover)
      -> engine servers (MeshWorkerNode × N: one ServeEngine each)
           ⇅ heartbeats (endpoint + load report)
    registry (CourierNode: membership, TTL eviction)

In the fabric, every engine replica registers its endpoint with the
``Registry`` and heartbeats a load report (free KV slots, queue depth,
EWMA us/token); each ``Router`` discovers the live set, dispatches every
request to the least-loaded replica, retries onto a sibling when a
replica dies mid-decode, and fails fast with the typed ``Overloaded``
when every replica is at its admission budget. All of it is plain
Launchpad nodes — thread, process, and test launchers wire it the same
way (see ``repro/serve/router.py``).

Two serving modes share the single-engine topology (``--mode``):

``continuous`` (default)
    The model server runs a :class:`repro.serve.engine.ServeEngine`: a
    persistent decode loop over a fixed pool of KV-cache slots. The
    batcher forwards each request as its own ``futures.generate`` RPC;
    the engine admits it into a free slot between decode steps and the
    reply streams back the moment that one sequence finishes.

``lockstep``
    The PR-3-era baseline, kept for paired A/B: the batcher drains up to
    ``max_batch`` queued prompts, pads them into one batch, and the
    server runs prefill+decode once per batch — every request waits for
    a batch boundary and the whole batch waits for its slowest member.
    Ragged groups are now served *correctly*: the batcher sends the true
    lengths and ``generate`` decodes each row at its own position, so
    pad tokens are never attended as context.

    PYTHONPATH=src python -m repro.launch.serve --requests 12
    PYTHONPATH=src python -m repro.launch.serve --mode lockstep
    PYTHONPATH=src python -m repro.launch.serve --replicas 2 --routers 1
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b  # published

With no ``--arch`` the program serves the reduced qwen2-1.5b preset;
``--arch NAME`` serves that architecture at its published widths and
``--reduced`` its reduced preset.
"""

from __future__ import annotations

import argparse
import collections
import json
import queue
import threading
import time

import numpy as np

from repro import configs, core as lp
from repro.core import telemetry
from repro.launch.cache import enable_compile_cache
from repro.models.config import ModelConfig
from repro.serve import decode as serve_lib
from repro.serve.router import Router, decorrelated_backoff, is_overloaded

# Bounded, thread-safe history for Batcher.stats(): the worker thread
# appends per-batch sizes while stats() RPCs read concurrently.
STATS_WINDOW = 256


class ModelServer:
    """Lockstep baseline: holds params; serves batched generate() on its mesh.

    ``prompts`` arrives over courier as a read-only array that may alias
    shared transport memory (the shm slot pool) — ``jnp.asarray`` device-
    puts straight from that view, so the Batcher -> ModelServer hop adds
    no extra host copy, and the slot frees when this call returns.
    """

    def __init__(self, model_cfg: ModelConfig, max_new: int = 8, mesh=None):
        import jax
        from repro.models import transformer
        self._cfg = model_cfg
        self._max_new = max_new
        self._params = transformer.init_params(model_cfg, jax.random.key(0))

    def generate(self, prompts, lengths=None):
        import jax.numpy as jnp
        toks = jnp.asarray(np.asarray(prompts, np.int32))
        out = serve_lib.generate(self._cfg, self._params, toks,
                                 max_new=self._max_new,
                                 context_len=toks.shape[1] + self._max_new,
                                 lengths=None if lengths is None
                                 else np.asarray(lengths, np.int32))
        return np.asarray(out)


class EngineServer:
    """Continuous-batching model server: a ServeEngine on this mesh worker.

    ``generate`` blocks its RPC handler thread until that one sequence
    retires — the courier server's handler pool is what lets many
    requests ride the engine concurrently, each reply streaming back
    per-request instead of per-batch.

    With ``registry`` set (the serve fabric), the server registers its
    own endpoint — learned from the worker context, no plumbing through
    the program — and heartbeats its live load report (``load()``:
    free slots, queue depth, EWMA us/token, loaded model version), which
    is the routers' routing signal *and* the rollout controller's version
    table. ``kill()`` crashes the replica in place (stops the engine
    *and* the heartbeats without deregistering): in-flight requests fail
    over, the registry evicts on missed beats — the failure path tests
    and the chaos demo drive exactly this. ``stall``/``drop`` are the
    FaultInjector's softer weapons (missed beats / transport blackhole
    for a window, then recovery).

    The replica lives on its mesh's one device (the launcher hands each
    mesh worker its own): params, KV state and the engine's device-resident
    feed state are placed there, so N replicas on an N-chip host use a
    chip each. The engine thread compiles its decode ladder (``warmup``)
    before it serves, and the replica registers with the registry only
    once that is done, so routers never dispatch to a replica that is
    still compiling; the seconds land in the ``engine.warmup_s``
    histogram. The constructor returns before the warm-up, so direct
    callers find the service at once and their requests queue.

    With ``store_dir`` set, weights load from a versioned
    :class:`~repro.ckpt.checkpoint.ModelStore` (``version=None`` means
    latest) instead of fresh init, and ``load_version()`` hot-swaps to
    another published version: the restore is checked against the
    current tree (shape identity — same architecture or the RPC fails,
    which is the rollout's health gate firing) and installed between
    decode windows, so the compiled ladder stays warm and in-flight
    requests keep decoding.
    """

    def __init__(self, model_cfg: ModelConfig, max_new: int = 8,
                 num_slots: int = 8, context_len: int | None = None,
                 eos_id: int | None = None, request_timeout_s: float = 120.0,
                 registry=None, heartbeat_s: float = 0.5,
                 name: str | None = None, endpoint: str | None = None,
                 mesh=None, sync_every: int = 8, decode_impl: str = "auto",
                 top_k: int | None = None,
                 prefill_chunk: int | None = None,
                 page_size: int | None = None,
                 num_pages: int | None = None,
                 prefix_cache: bool = True,
                 store_dir: str | None = None,
                 version: int | None = None):
        import contextlib
        import jax
        from repro.models import transformer
        from repro.serve.engine import ServeEngine
        self._cfg = model_cfg
        self._timeout = request_timeout_s
        self._store = None
        self._version: int | None = None
        self._drop_until = 0.0
        device = None
        if mesh is not None:
            if mesh.size != 1:
                raise ValueError(
                    f"EngineServer runs one replica on one device; got a "
                    f"{mesh.size}-device mesh {dict(mesh.shape)}")
            device = mesh.devices.flat[0]
        on_device = (contextlib.nullcontext() if device is None
                     else jax.default_device(device))
        with on_device:
            params = transformer.init_params(model_cfg, jax.random.key(0))
        if store_dir is not None:
            from repro.ckpt.checkpoint import ModelStore
            self._store = ModelStore(store_dir)
            v = self._store.latest_version() if version is None else version
            if v is None:
                raise ValueError(f"model store {store_dir!r} has no "
                                 "published versions")
            # The store returns host arrays; the engine lives where its
            # params are.
            params = jax.device_put(
                self._store.load_version(int(v), like=params), device)
            self._version = int(v)
        self._engine = ServeEngine(
            model_cfg, params, num_slots=num_slots,
            context_len=context_len or 128,
            max_new=max_new, eos_id=eos_id, sync_every=sync_every,
            decode_impl=decode_impl, top_k=top_k,
            prefill_chunk=prefill_chunk, page_size=page_size,
            num_pages=num_pages, prefix_cache=prefix_cache)
        self._engine.start(warmup=True)
        self._heartbeater = None
        if registry is not None:
            ctx = lp.get_current_context()
            name = name or ctx.node_name
            endpoint = endpoint or ctx.endpoint
            if endpoint is None:
                raise ValueError(
                    "EngineServer(registry=...) needs a serving endpoint: "
                    "run it as a courier-serving node or pass endpoint=")
            self._heartbeater = lp.Heartbeater(
                registry, name, endpoint, load_fn=self.load,
                period_s=heartbeat_s, stop_event=ctx.stop_event)
            threading.Thread(target=self._join_fabric,
                             args=(ctx.stop_event,), daemon=True,
                             name=f"join/{name}").start()

    def _join_fabric(self, stop_event) -> None:
        """Start heartbeats (registration) once the engine is warm."""
        while not (self._engine.ready.wait(0.1) or stop_event.is_set()):
            pass
        if self._engine.alive and not stop_event.is_set():
            self._heartbeater.start()

    def run(self):
        """Serve until the program stops, then stop the engine: its thread
        exits and the replica's device memory is released."""
        lp.get_current_context().wait_for_stop()
        self._engine.stop()

    def generate(self, prompt, max_new=None):
        if time.monotonic() < self._drop_until:
            raise ConnectionError("transport drop (fault injection)")
        fut = self._engine.submit(np.asarray(prompt, np.int32).reshape(-1),
                                  max_new=max_new)
        from concurrent import futures as cf
        try:
            return fut.result(timeout=self._timeout)
        except cf.TimeoutError:
            # A queued (not yet admitted) request is cancellable: don't
            # let an abandoned reply go on to occupy a slot.
            fut.cancel()
            raise

    def load(self):
        """The routing signal: free slots, queued requests, EWMA us/token —
        plus the loaded model version, which the heartbeat carries into
        the Registry's version table (the rollout's source of truth)."""
        report = self._engine.load()
        if self._version is not None:
            report["version"] = self._version
        return report

    def health(self):
        status = "ok" if self._engine.alive else "stopped"
        return {"status": status, **self.load()}

    def load_version(self, version):
        """Hot-swap to a published model version (the rollout's swap
        step). Restores against the current tree — a version published
        for a different architecture fails *here*, before any weight is
        installed — then applies between decode windows."""
        if self._store is None:
            raise RuntimeError("EngineServer has no model store attached "
                               "(pass store_dir=)")
        params = self._store.load_version(int(version),
                                          like=self._engine._params)
        self._engine.swap_params(params)
        self._version = int(version)
        if self._heartbeater is not None:
            # Don't wait a beat period to advertise the new version.
            self._heartbeater.beat_now()
        return {"version": self._version}

    def stall(self, seconds: float):
        """Fault hook: miss heartbeats for ``seconds`` — the registry
        TTL-evicts this replica, then its resumed beats re-register it
        (the stall → evict → revive cycle). The engine keeps serving
        whatever is already in flight."""
        telemetry.record_event("stall", cause=f"heartbeats paused "
                               f"{seconds}s (fault injection)")
        if self._heartbeater is not None:
            self._heartbeater.pause(seconds)
        return "stalled"

    def drop(self, seconds: float):
        """Fault hook: blackhole the request transport for ``seconds`` —
        ``generate`` raises ``ConnectionError``, routers fail over and
        report the failure; heartbeats continue, so the replica
        re-registers and recovers once the window passes."""
        telemetry.record_event("drop", cause=f"transport blackholed "
                               f"{seconds}s (fault injection)")
        self._drop_until = time.monotonic() + float(seconds)
        return "dropped"

    def kill(self):
        """Simulate a replica crash: stop heartbeats (no deregistration)
        and the engine, failing everything in flight. The fabric's job is
        to make this invisible to clients."""
        telemetry.record_event("kill", cause="replica killed "
                               "(fault injection)")
        if self._heartbeater is not None:
            self._heartbeater.stop(deregister=False)
        self._engine.stop()
        return "killed"

    def stats(self):
        return self._engine.stats()

    def telemetry(self):
        """Telemetry scrape target: process metrics + drained span/event
        rings, with the engine's full counter set as the service payload
        (the hub files it per node name)."""
        return telemetry.telemetry_snapshot(service=self._engine.stats())


class Batcher:
    """Admission front for the model server.

    ``mode="continuous"``: thin pass-through — each ``submit`` forwards
    the prompt as its own ``futures.generate`` RPC and blocks its handler
    thread for that one reply; all batching happens inside the engine at
    decode-step granularity.

    ``mode="lockstep"``: the classic coalescing worker (the A/B
    baseline). The model server is driven through ``futures.generate`` so
    the batcher thread goes straight back to coalescing the next group
    while the mesh is still computing the previous one (bounded by
    ``max_inflight``). Queued prompts are kept as the transport handed
    them over (zero-copy views) and copied exactly once into the padded
    batch; the true lengths ride along so ragged groups decode at their
    own positions instead of attending to pad tokens.
    """

    def __init__(self, server, max_batch: int = 8, max_wait_s: float = 0.02,
                 max_inflight: int = 2, mode: str = "continuous",
                 request_timeout_s: float = 150.0):
        if mode not in ("continuous", "lockstep"):
            raise ValueError(f"unknown serve mode {mode!r}")
        self._server = server
        self._mode = mode
        # Above the engine server's own per-request timeout, so a server-
        # side timeout surfaces as the reply instead of racing this one.
        self._timeout = request_timeout_s
        self._q: queue.Queue = queue.Queue()
        self._max_batch = max_batch
        self._max_wait = max_wait_s
        self._inflight = threading.Semaphore(max_inflight)
        self._stats_lock = threading.Lock()
        self._batches = collections.deque(maxlen=STATS_WINDOW)
        self._submitted = 0
        if mode == "lockstep":
            self._worker = threading.Thread(target=self._loop, daemon=True)
            self._worker.start()

    def submit(self, prompt):
        """Blocking request: returns the completed sequence."""
        with self._stats_lock:
            self._submitted += 1
        if self._mode == "continuous":
            # Thin admission: one request, one RPC, one streamed reply.
            fut = self._server.futures.generate(
                np.asarray(prompt, np.int32))
            return fut.result(timeout=self._timeout)
        done = queue.Queue(maxsize=1)
        # asarray, not array: an int32 prompt (incl. a transport-owned
        # view) is queued as-is; the one copy happens in _loop's stack.
        self._q.put((np.asarray(prompt, np.int32), done))
        out = done.get(timeout=self._timeout)
        if isinstance(out, BaseException):
            raise out
        return out

    def _loop(self):
        while True:
            first = self._q.get()
            group = [first]
            deadline = time.monotonic() + self._max_wait
            while len(group) < self._max_batch:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    group.append(self._q.get(timeout=remaining))
                except queue.Empty:
                    break
            # One copy per prompt: transport views -> the padded batch
            # (right-padded with 0 when lengths differ). Rebinding
            # ``group`` to the reply queues drops this thread's prompt
            # references before the batch RPC goes out.
            lengths = np.array([len(g[0]) for g in group], np.int32)
            prompts = np.zeros((len(group), int(lengths.max())), np.int32)
            for row, (p, _) in zip(prompts, group):
                row[:len(p)] = p
            group = [done for _, done in group]
            self._inflight.acquire()
            fut = self._server.futures.generate(prompts, lengths)
            with self._stats_lock:
                self._batches.append(len(group))
            fut.add_done_callback(
                lambda f, group=group: self._deliver(group, f))

    def _deliver(self, group, fut):
        self._inflight.release()
        try:
            outs = fut.result()
        except BaseException as exc:  # noqa: BLE001 - fail the waiters
            for done in group:
                done.put(exc)
            return
        for done, row in zip(group, outs):
            done.put(row)

    def stats(self):
        with self._stats_lock:
            return {"mode": self._mode,
                    "submitted": self._submitted,
                    "batches": list(self._batches)}


class Client:
    """Closed-loop client with a bounded pipeline window.

    Requests go out as ``futures.submit`` with up to ``window`` in flight
    (rather than one blocking RPC per request), which is what actually
    gives the serving side concurrent prompts. Latency samples are
    flushed to the meter in a single ``batch_call`` — N records, one frame.
    """

    def __init__(self, batcher, meter, num_requests: int, prompt_len: int,
                 vocab: int, seed: int, window: int = 4, source: str = "",
                 trace_every: int = 0):
        self._batcher = batcher
        self._meter = meter
        self._n = num_requests
        self._rng = np.random.default_rng(seed)
        self._plen = prompt_len
        self._vocab = vocab
        self._window = max(1, window)
        # Which admission front this client talks to (router/batcher node
        # label) — the meter namespaces its percentiles by it.
        self._source = source
        # Trace sampling: every Nth request carries a trace envelope (0 =
        # off). The sampled request's root "request" span is the measured
        # e2e window every downstream span must account for.
        self._trace_every = max(0, int(trace_every))

    def _submit(self, prompt, trace):
        if trace is None:
            return self._batcher.futures.submit(prompt)
        # Current-thread context drives injection at the courier proxy;
        # the envelope's parent is the pre-minted root span id, so every
        # remote span nests under the "request" root.
        with telemetry.activate(trace[0].child(trace[1])):
            return self._batcher.futures.submit(prompt)

    def run(self):
        pending: list[tuple] = []
        records: list[tuple[float, int]] = []

        def drain_one():
            t0, prompt, fut, trace = pending.pop(0)
            backoff = 0.0
            while True:
                try:
                    out = fut.result(timeout=120)
                    break
                except BaseException as exc:  # noqa: BLE001
                    # Overloaded is the fabric's retry-later signal;
                    # latency keeps accruing from the first attempt.
                    # Decorrelated jitter on the resubmit: every client
                    # sees Overloaded at the same moment when capacity
                    # dips (a drain, a kill) — a fixed schedule would
                    # have them all stampede back on the same tick.
                    if not is_overloaded(exc):
                        raise
                    backoff = decorrelated_backoff(backoff, self._rng,
                                                   base_s=0.005, cap_s=0.2)
                    time.sleep(backoff)
                    fut = self._submit(prompt, trace)
            if trace is not None:
                ctx, root_sid, t0w, t0p = trace
                telemetry.record_span("request", ctx, t0w,
                                      time.perf_counter() - t0p,
                                      span_id=root_sid, root=True,
                                      out_len=len(out))
            records.append((time.monotonic() - t0, len(out)))

        for k in range(self._n):
            while len(pending) >= self._window:
                drain_one()
            prompt = self._rng.integers(0, self._vocab, self._plen,
                                        dtype=np.int32)
            trace = None
            if self._trace_every and k % self._trace_every == 0:
                trace = (telemetry.start_trace(), telemetry.new_span_id(),
                         time.time(), time.perf_counter())
            pending.append((time.monotonic(), prompt,
                            self._submit(prompt, trace), trace))
        while pending:
            drain_one()
        self._meter.batch_call(
            [("record", (lat, out_len), {"source": self._source})
             for lat, out_len in records])


class Meter:
    """Collects request latencies; prints percentiles and (optionally)
    writes the summary to a JSON file before stopping the program. The
    summary also carries the shortest and longest reply
    (``out_len_min``/``out_len_max``, prompt + generated tokens).

    Built on the telemetry histogram layer: every record lands in a
    per-source :class:`repro.core.telemetry.Histogram` registered as
    ``meter.latency_ms.<source>`` in the process metrics registry — so
    the same numbers the meter prints are scrapable through any
    ``telemetry()`` RPC, and the summary's count/mean are exact while
    p50/p95 are log2-bucket approximations (<= ~4.5% relative error, the
    histogram's bucket width). The summary JSON keeps its shape: the
    top-level keys are the merged roll-up row (histograms merge by
    bucket) with per-source summaries namespaced under ``per_source``.

    ``holds`` delays the program stop past the last served request: each
    hold is dropped by a ``release()`` RPC, and the stop fires only once
    the count is reached AND every hold is released. The rollout demo
    uses one hold so a RolloutDriver that gets scheduled late (starved
    thread on a loaded host) still finds the fleet's courier services
    registered instead of racing program teardown.
    """

    def __init__(self, expected: int, summary_path: str | None = None,
                 holds: int = 0):
        self._expected = expected
        self._summary_path = summary_path
        self._hists: dict[str, telemetry.Histogram] = {}
        self._out_len = telemetry.Histogram("meter.out_len")  # exact min/max
        self._count = 0
        self._holds = holds
        self._summary_done = False
        self._lock = threading.Lock()

    @staticmethod
    def _percentiles(h: telemetry.Histogram) -> dict:
        return {"count": int(h.count),
                "p50_ms": float(h.percentile(50)),
                "p95_ms": float(h.percentile(95)),
                "mean_ms": float(h.mean)}

    def record(self, latency_s: float, out_len: int, source: str = ""):
        with self._lock:
            src = source or "default"
            h = self._hists.get(src)
            if h is None:
                h = telemetry.metrics().histogram(f"meter.latency_ms.{src}")
                # This meter's lifetime scopes the window: the registry
                # entry may survive from a previous program in the same
                # process (thread launcher, tests) and must not leak its
                # counts into this run's summary.
                h.reset()
                self._hists[src] = h
            h.record(latency_s * 1e3)       # stored in ms: keys read direct
            self._out_len.record(out_len)
            self._count += 1
            done = self._count >= self._expected and not self._summary_done
            if done:
                self._summary_done = True
            stop = self._count >= self._expected and self._holds == 0
        if done:
            merged = telemetry.Histogram("meter.latency_ms")
            for h in self._hists.values():
                merged.merge(h)
            summary = self._percentiles(merged)   # the merged roll-up row
            summary["out_len_min"] = int(self._out_len.vmin)
            summary["out_len_max"] = int(self._out_len.vmax)
            if len(self._hists) > 1 or "default" not in self._hists:
                summary["per_source"] = {
                    src: self._percentiles(h)
                    for src, h in sorted(self._hists.items())}
            print(f"served {summary['count']} requests: "
                  f"p50={summary['p50_ms']:.1f}ms "
                  f"p95={summary['p95_ms']:.1f}ms")
            if self._summary_path:
                with open(self._summary_path, "w") as f:
                    json.dump(summary, f, indent=2)
                    f.write("\n")
        if stop:
            lp.stop_program()

    def telemetry(self):
        """Scrape target (explicit hub handle in the fabric program)."""
        return telemetry.telemetry_snapshot()

    def release(self, tag: str = "") -> None:
        """Drop one stop-hold (e.g. the RolloutDriver finished its roll)."""
        with self._lock:
            self._holds = max(0, self._holds - 1)
            stop = self._count >= self._expected and self._holds == 0
        if stop:
            lp.stop_program()


def build_program(model_cfg: ModelConfig, *, num_clients=3,
                  requests_per_client=4, prompt_len=8, max_new=8,
                  mode: str = "continuous", num_slots: int = 8,
                  meter_json: str | None = None, replicas: int = 1,
                  routers: int = 0, registry_ttl_s: float = 2.0,
                  heartbeat_s: float = 0.25,
                  kill_after: int | None = None,
                  page_size: int | None = None,
                  num_pages: int | None = None,
                  store_dir: str | None = None,
                  model_version: int | None = None,
                  rollout: int | None = None,
                  rollout_after: int | None = None,
                  canary_fraction: float = 0.25,
                  telemetry_dir: str | None = None,
                  trace_every: int = 0) -> lp.Program:
    """Wire the serving topology as a Launchpad program.

    ``routers == 0`` (default) is the direct PR-4 path — one engine (or
    the lockstep baseline) behind a Batcher; ``replicas`` must be 1.
    ``routers >= 1`` builds the replicated serve fabric:
    Registry -> Routers -> EngineServers, clients partitioned across
    routers round-robin. ``kill_after`` adds a FaultInjector node that
    kills replica 0 once that many requests have been served — mid-run
    by construction (the failover demo: traffic must keep flowing).

    ``store_dir`` points the engines at a versioned ModelStore
    (``model_version`` picks the starting version; None = latest), and
    ``rollout=V`` adds a RolloutController node that rolls the fleet to
    version ``V`` once ``rollout_after`` requests have been served —
    drain, hot-swap, canary-compare, promote (or roll back), while the
    clients' traffic keeps completing.

    ``telemetry_dir`` adds a TelemetryHub node (fabric topology only)
    that scrapes every replica through the registry — plus the routers
    and meter by handle — and writes ``telemetry.json`` +
    ``trace.json`` (Perfetto) there. ``trace_every=N`` makes every
    client trace its every Nth request end to end.
    """
    p = lp.Program(f"serve-{model_cfg.name}")
    total = num_clients * requests_per_client

    if routers < 1:
        if replicas != 1:
            raise ValueError("replicas > 1 needs at least one router "
                             "(--routers 1)")
        if kill_after is not None:
            raise ValueError("the failover demo needs the fabric "
                             "(--routers >= 1 and --replicas >= 2)")
        with p.group("server"):
            if mode == "continuous":
                server = p.add_node(lp.MeshWorkerNode(
                    EngineServer, model_cfg, max_new=max_new,
                    num_slots=num_slots, context_len=prompt_len + max_new,
                    page_size=page_size, num_pages=num_pages))
            else:
                server = p.add_node(lp.MeshWorkerNode(ModelServer, model_cfg,
                                                      max_new=max_new))
        with p.group("batcher"):
            batcher = p.add_node(lp.CourierNode(Batcher, server, mode=mode))
        meter = p.add_node(lp.CourierNode(Meter, total,
                                          summary_path=meter_json))
        with p.group("client"):
            for i in range(num_clients):
                p.add_node(lp.CourierNode(
                    Client, batcher, meter, requests_per_client, prompt_len,
                    model_cfg.vocab_size, seed=i, trace_every=trace_every))
        return p

    if mode != "continuous":
        raise ValueError("the serve fabric routes to continuous-batching "
                         "engines only (drop --mode lockstep)")
    if kill_after is not None and replicas < 2:
        raise ValueError("killing a replica with no sibling loses requests "
                         "by construction; use --replicas >= 2")
    if kill_after is not None and kill_after >= total:
        raise ValueError(f"--kill-after {kill_after} never fires: only "
                         f"{total} requests will be served")
    if rollout is not None:
        if store_dir is None:
            raise ValueError("rollout= needs store_dir= (a ModelStore with "
                             "the target version published)")
        if rollout_after is None or rollout_after >= total:
            raise ValueError("rollout= needs rollout_after < total requests "
                             "so the roll happens under load")

    with p.group("registry"):
        registry = p.add_node(lp.CourierNode(lp.Registry,
                                             ttl_s=registry_ttl_s))
    replica_handles = []
    with p.group("server"):
        for _ in range(replicas):
            replica_handles.append(p.add_node(lp.MeshWorkerNode(
                EngineServer, model_cfg, max_new=max_new,
                num_slots=num_slots, context_len=prompt_len + max_new,
                page_size=page_size, num_pages=num_pages,
                registry=registry, heartbeat_s=heartbeat_s,
                store_dir=store_dir, version=model_version)))
    router_nodes, router_handles = [], []
    with p.group("router"):
        for _ in range(routers):
            node = lp.CourierNode(Router, registry,
                                  refresh_s=heartbeat_s)
            router_handles.append(p.add_node(node))
            router_nodes.append(node)
    meter = p.add_node(lp.CourierNode(Meter, total, summary_path=meter_json,
                                      holds=1 if rollout is not None else 0))
    with p.group("client"):
        for i in range(num_clients):
            m = i % routers
            p.add_node(lp.CourierNode(
                Client, router_handles[m], meter, requests_per_client,
                prompt_len, model_cfg.vocab_size, seed=i,
                source=router_nodes[m].name, trace_every=trace_every))
    if telemetry_dir is not None:
        with p.group("telemetry"):
            p.add_node(lp.PyNode(
                lp.TelemetryHub, registry,
                targets=list(router_handles) + [meter, registry],
                poll_s=max(heartbeat_s, 0.1), out_dir=telemetry_dir))
    if kill_after is not None:
        with p.group("chaos"):
            p.add_node(lp.PyNode(
                lp.FaultInjector,
                [lp.FaultEvent(kind="kill", target=0,
                               after_served=kill_after)],
                [replica_handles[0]], progress=list(router_handles)))
    if rollout is not None:
        with p.group("rollout"):
            p.add_node(lp.PyNode(RolloutDriver, registry,
                                 list(router_handles), rollout,
                                 rollout_after,
                                 canary_fraction=canary_fraction,
                                 meter=meter))
    return p


class RolloutDriver:
    """Program node that triggers a fleet rollout mid-run: once the
    routers have completed ``after_served`` requests (count-based, like
    the FaultInjector's kill trigger — lands mid-run on any host speed),
    it runs a :class:`~repro.serve.rollout.RolloutController` against the
    registry. All rollout state lives in the registry's version table, so
    this node restarting just re-runs ``rollout()`` and resumes.

    The driver pins the program open: it holds one Meter stop-hold (see
    ``Meter.release``) until its roll completes, so the fleet's courier
    services are guaranteed to still be registered when it runs — even
    when this thread is scheduled so late (loaded host) that every
    request has already been served."""

    def __init__(self, registry, routers, version: int, after_served: int,
                 canary_fraction: float = 0.25, canary_requests: int = 4,
                 canary_timeout_s: float = 5.0, meter=None):
        self._registry = registry
        self._routers = routers
        self._version = version
        self._after = after_served
        self._canary_fraction = canary_fraction
        self._canary_requests = canary_requests
        self._canary_timeout = canary_timeout_s
        self._meter = meter

    def run(self):
        from repro.serve.rollout import RolloutController
        ctx = lp.get_current_context()
        try:
            while not ctx.wait_for_stop(0.002):
                try:
                    done = sum(r.stats()["completed"]
                               for r in self._routers)
                except Exception:
                    # Bring-up race: routers register their courier
                    # services asynchronously, and on a loaded host that
                    # can outlast one lookup window. Transient here —
                    # keep polling instead of taking the program down
                    # (launch_and_wait runs fail-fast, max_restarts=0).
                    continue
                if done < self._after:
                    continue
                result = RolloutController(
                    self._registry, self._routers,
                    canary_fraction=self._canary_fraction,
                    canary_requests=self._canary_requests,
                    canary_timeout_s=self._canary_timeout,
                ).rollout(self._version)
                print(f"rollout: {result['status']} -> v{self._version} "
                      f"in {result.get('duration_s', 0.0):.2f}s", flush=True)
                return
        finally:
            if self._meter is not None:
                try:
                    self._meter.release("rollout")
                except Exception:
                    pass


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="serve this architecture at its published widths "
                         "(default: the reduced qwen2-1.5b preset)")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the reduced preset of --arch")
    ap.add_argument("--clients", type=int, default=3)
    ap.add_argument("--requests", type=int, default=4,
                    help="requests per client")
    ap.add_argument("--mode", choices=("continuous", "lockstep"),
                    default="continuous")
    ap.add_argument("--slots", type=int, default=8,
                    help="KV-cache slots (continuous mode)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged KV mode: tokens per page (None = flat)")
    ap.add_argument("--pages", type=int, default=None,
                    help="paged KV mode: pool size in pages "
                         "(default slots * ceil(context/page_size))")
    ap.add_argument("--meter-json", default=None,
                    help="write the latency percentile summary here")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas (>1 needs --routers >= 1)")
    ap.add_argument("--routers", type=int, default=0,
                    help="fabric routers; 0 = direct single-engine path")
    ap.add_argument("--kill-after", type=int, default=None, metavar="N",
                    help="failover demo: kill replica 0 after N requests "
                         "have been served (deterministically mid-run)")
    ap.add_argument("--store", default=None,
                    help="ModelStore directory (created and seeded with "
                         "v0/v1 for the rollout demo when absent)")
    ap.add_argument("--rollout-after", type=int, default=None, metavar="N",
                    help="rollout demo: roll the fleet v0 -> v1 after N "
                         "requests (needs the fabric; publishes both "
                         "versions into --store first)")
    ap.add_argument("--telemetry-dir", default=None, metavar="DIR",
                    help="fabric only: run a TelemetryHub and write "
                         "telemetry.json + trace.json (Perfetto) here")
    ap.add_argument("--trace-every", type=int, default=0, metavar="N",
                    help="trace every Nth request per client end to end "
                         "(0 = tracing off)")
    args = ap.parse_args(argv)
    if args.arch and not args.reduced:
        cfg = configs.get(args.arch)
    else:
        cfg = configs.get_reduced(args.arch or "qwen2-1.5b")
    enable_compile_cache()
    store_dir, model_version, rollout = args.store, None, None
    if args.rollout_after is not None:
        import tempfile
        import jax
        from repro.ckpt.checkpoint import ModelStore, config_hash
        from repro.models import transformer
        store_dir = store_dir or tempfile.mkdtemp(prefix="modelstore-")
        store = ModelStore(store_dir)
        for v in (0, 1):
            if v not in store.versions():
                store.publish_version(
                    v, transformer.init_params(cfg, jax.random.key(v)),
                    metadata={"step": v, "config_hash": config_hash(cfg)})
        model_version, rollout = 0, 1
    program = build_program(cfg, num_clients=args.clients,
                            requests_per_client=args.requests,
                            mode=args.mode, num_slots=args.slots,
                            meter_json=args.meter_json,
                            replicas=args.replicas, routers=args.routers,
                            kill_after=args.kill_after,
                            page_size=args.page_size, num_pages=args.pages,
                            store_dir=store_dir, model_version=model_version,
                            rollout=rollout,
                            rollout_after=args.rollout_after,
                            telemetry_dir=args.telemetry_dir,
                            trace_every=args.trace_every)
    print(program)
    lp.launch_and_wait(program, timeout_s=600)


if __name__ == "__main__":
    main()
