#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/mixes/<traffic>.json``); each metric is read by
``bench/metrics/<name>.py``. The configuration names, by path, its plain
reference (``reference``: ``Q_BLOCK`` and ``logits_at``) and its weight
maker (``weights``: ``make`` and ``param_count``), so a new architecture
brings its own two files. A new cell, mix, metric or architecture is a new
file and a new entry, never an edit here.

What a run does:

1. names the device (platform, ``device_kind``, count) and exits 1 without
   a result where there is no TPU or fewer chips than the cell asks for;
2. launches the served path as a Launchpad program under the thread
   launcher: Registry -> Router -> one EngineServer per chip, and this
   benchmark's own driver node, which talks to the router through the
   courier ``futures`` client. The engines' weights come from
   ``bench/weights.py`` and the seed;
3. set-up (``setup_s``, from process start): weights, each engine's
   decode ladder, and every prefill shape of the mix, sent straight to
   each replica; shared system prompts get registered on the way;
4. the window, ``--seconds`` long: an open loop sends each request when it
   is due (latency is due-to-reply); a closed loop keeps every caller's
   next request waiting behind its last;
5. with ``--trace 1`` the middle ten seconds of the window run under
   ``jax.profiler`` and the run prints the per-layer metrics instead of
   the end-to-end ones;
6. stops the program, reads the peak device memory, and compares a sample
   of the finished requests with the plain float32 reference
   (``bench/reference.py``): every served token's reference logit must lie
   within the configuration's limit of the reference's best.

The last stdout line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``, each number compared beside its limit.

``--control 1`` puts the reference's float8 control in the program's place
for the comparison (the served window runs as usual): the tokens compared
are those the control puts first at each served position, and the run must
come out not correct. The benchmark's own runs never pass it.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import functools         # noqa: E402
import gc                # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import pathlib           # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import threading         # noqa: E402
import types             # noqa: E402
from concurrent import futures as cf  # noqa: E402
from typing import Any, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / "bench_out"                # traces, written and removed per run
# JAX's persistent compile cache, at a fixed path inside the checkout: only
# a cell's first run there compiles, and two checkouts never share one.
CACHE = ROOT / ".jax_cache"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
WARM_PARALLEL = 4          # warm-up requests in flight per replica
TRACE_S = 10.0             # seconds of the window that a traced run records


@dataclasses.dataclass
class Rec:
    """One request of the window, as the caller saw it. Times are seconds
    from the window's start."""
    prompt: Any
    max_new: int
    shared: int = 0
    due_s: Optional[float] = None       # open loop only
    sent: Optional[float] = None
    done: Optional[float] = None
    ok: bool = False
    out: Any = None
    error: str = ""

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def out_tokens(self) -> int:
        return 0 if self.out is None else len(self.out) - len(self.prompt)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one finished run."""
    cell: dict
    model: dict
    mix: dict
    chips: int
    num_slots: int
    peaks: dict
    window_s: float
    setup_s: float = 0.0
    requests: list = dataclasses.field(default_factory=list)
    engine: dict = dataclasses.field(default_factory=dict)
    router: dict = dataclasses.field(default_factory=dict)
    ttft_us: Any = None
    matmul_params: int = 0              # weights that multiply activations
    compiles_in_window: int = 0
    events: list = dataclasses.field(default_factory=list)
    busy_s: float = 0.0
    trace_window_s: float = 0.0
    breakdown: Optional[dict] = None


# -- the benchmark's files -------------------------------------------------

def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"bench/run.py: no workload {name!r} in BENCHMARK.json")


def load_config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise SystemExit(f"bench/run.py: no configuration {name!r}")


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


@functools.lru_cache(maxsize=None)
def load_module(path: pathlib.Path):
    """The module in the file ``path``, loaded once per process."""
    spec = importlib.util.spec_from_file_location(
        "bench._" + "_".join(path.relative_to(ROOT).with_suffix("").parts)
        .replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_module(cfg: dict, key: str):
    """The module that the configuration names under ``key``
    (``reference`` or ``weights``), a path from the checkout's root."""
    if key not in cfg:
        raise SystemExit(f"bench/run.py: configuration {cfg['name']!r} "
                         f"names no {key!r} module")
    return load_module(ROOT / cfg[key])


def reader(name: str, metrics_dir: pathlib.Path = BENCH / "metrics"):
    """``read`` of ``metrics/<name>.py``, else of ``metrics/<base>.py`` for
    a name ``<base>.<suffix>``."""
    for stem in (name, name.split(".")[0]):
        path = metrics_dir / f"{stem}.py"
        if path.exists():
            return load_module(path).read
    raise SystemExit(f"bench/run.py: no reader for metric {name!r}")


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"bench/run.py: device kind {kind!r} is not in "
                         "bench/peaks.json; add its published peaks")
    return table[kind]


# -- the served program ------------------------------------------------------

def model_config(cfg: dict):
    from repro.models.config import ModelConfig
    kw = dict(cfg["model"])
    kw["pattern"] = tuple(kw["pattern"])
    return ModelConfig(**kw)


def serve_seeded_weights(cfg: dict, seed: int) -> None:
    """Make the engines' weights the benchmark's: ``EngineServer`` builds
    its parameters with ``transformer.init_params``, and in this process
    that now returns the configuration's weight maker's tree for ``seed``,
    made in one jitted call on the caller's default device (the replica's
    chip)."""
    from repro.models import transformer
    maker = config_module(cfg, "weights")
    transformer.init_params = lambda _cfg, _key: maker.make(
        cfg["model"], seed, cfg["weights_dtype"])


class Driver:
    """Program node: set-up through each replica, then the window through
    the router. ``job`` does the work; the program stops when it returns."""

    def __init__(self, router, replicas, job, state):
        self._router, self._replicas = router, replicas
        self._job, self._state = job, state

    def run(self):
        from repro import core as lp
        try:
            self._job(self._router, self._replicas, self._state)
        except BaseException as exc:        # noqa: BLE001
            self._state.error = exc
        finally:
            lp.stop_program()


def build_program(cfg: dict, replicas: int, job, state):
    from repro import core as lp
    from repro.launch.serve import EngineServer
    from repro.serve.router import Router
    eng = cfg["engine"]
    mc = model_config(cfg)
    p = lp.Program(f"bench-{cfg['name']}")
    with p.group("registry"):
        registry = p.add_node(lp.CourierNode(lp.Registry, ttl_s=30.0))
    handles = []
    with p.group("server"):
        for _ in range(replicas):
            handles.append(p.add_node(lp.MeshWorkerNode(
                EngineServer, mc, max_new=1,
                num_slots=eng["num_slots"], context_len=eng["context_len"],
                page_size=eng.get("page_size"),
                prefill_chunk=eng.get("prefill_chunk"),
                prefix_cache=eng.get("prefix_cache", True),
                sync_every=eng.get("sync_every", 8),
                request_timeout_s=300.0, registry=registry,
                heartbeat_s=0.5)))
    with p.group("router"):
        router = p.add_node(lp.CourierNode(Router, registry, refresh_s=0.5,
                                           request_timeout_s=300.0))
    with p.group("driver"):
        p.add_node(lp.PyNode(Driver, router, handles, job, state))
    return p


def wait_for_replicas(router, replicas, timeout_s: float = 1200.0) -> None:
    """Until the router sees every replica; a replica whose engine died
    (a warm-up that failed) ends the wait at once."""
    deadline = time.monotonic() + timeout_s
    while len(router.stats()["replicas"]) < len(replicas):
        for rep in replicas:
            try:
                status = rep.health()["status"]
            except RuntimeError:            # its service is not up yet
                continue
            if status != "ok":
                raise RuntimeError("a replica's engine stopped before it "
                                   "registered (its warm-up failed)")
        if time.monotonic() > deadline:
            raise RuntimeError(f"only {len(router.stats()['replicas'])} of "
                               f"{len(replicas)} replicas registered")
        time.sleep(0.2)


def warm(replicas, phases) -> None:
    """Send every warm-up phase to every replica directly, a phase at a
    time, a few requests in flight: each prefill shape compiles (or loads
    from the persistent cache) here and not in the window."""
    def one(rep):
        for phase in phases:
            for i in range(0, len(phase), WARM_PARALLEL):
                futs = [rep.futures.generate(r.prompt, max_new=r.max_new)
                        for r in phase[i:i + WARM_PARALLEL]]
                for f in futs:
                    f.result(timeout=1200)
    with cf.ThreadPoolExecutor(len(replicas)) as ex:
        list(ex.map(one, replicas))


def engine_counters(replicas) -> dict:
    keys = ("steps", "occupancy_sum", "decode_tokens", "generated_tokens",
            "prefix_tokens_reused", "admitted", "host_syncs")
    tot = dict.fromkeys(keys, 0)
    for rep in replicas:
        s = rep.stats()
        for k in keys:
            tot[k] += s.get(k, 0)
    return tot


def router_counters(router) -> dict:
    s = router.stats()
    return {"dispatches": s["dispatches"], "frames": s["frames"],
            "dispatch_us_sum": s["mean_dispatch_us"] * s["dispatches"]}


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


# -- the window ---------------------------------------------------------------

def _finish(rec: Rec, t0: float, fut) -> None:
    rec.done = time.monotonic() - t0
    try:
        rec.out = fut.result()
        rec.ok = True
    except BaseException as exc:            # noqa: BLE001
        rec.error = repr(exc)[:300]


def open_loop(router, recs: list[Rec], seconds: float, grace_s: float,
              on_close=None) -> float:
    """Send each request at its due time; at the window's close call
    ``on_close``, then wait for every reply up to ``grace_s`` past it.
    Returns the window's start."""
    t0 = time.monotonic()
    futs = []
    for rec in recs:
        delay = t0 + rec.due_s - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        rec.sent = time.monotonic() - t0
        f = router.futures.submit(rec.prompt, rec.max_new)
        f.add_done_callback(functools.partial(_finish, rec, t0))
        futs.append(f)
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    if on_close is not None:
        on_close()
    cf.wait(futs, timeout=max(0.0, t0 + seconds + grace_s - time.monotonic()))
    for rec in recs:
        if rec.done is None:
            rec.error = rec.error or "no reply within the grace period"
    return t0


def closed_loop(router, streams: list[list[Rec]], seconds: float,
                out: list[Rec]) -> float:
    """Every caller sends its next request when its last one returns, until
    the window closes. Requests still in flight then are not counted."""
    t0 = time.monotonic()
    end = t0 + seconds
    lock = threading.Lock()

    def send(c: int, i: int) -> None:
        stream = streams[c]
        rec = stream[i % len(stream)]
        if i >= len(stream):
            rec = dataclasses.replace(rec, out=None, done=None, ok=False)
        rec.sent = time.monotonic() - t0
        with lock:
            out.append(rec)
        f = router.futures.submit(rec.prompt, rec.max_new)
        f.add_done_callback(functools.partial(done, c, i, rec))

    def done(c: int, i: int, rec: Rec, fut) -> None:
        _finish(rec, t0, fut)
        if time.monotonic() < end:
            send(c, i + 1)

    for c in range(len(streams)):
        send(c, 0)
    time.sleep(max(0.0, end - time.monotonic()))
    return t0


class CompileCounter:
    """Counts ``jax.monitoring`` backend-compile events while armed."""

    def __init__(self):
        import jax
        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event == BACKEND_COMPILE:
            self.count += 1


def make_job(cfg: dict, mix: dict, seconds: float, seed: int, replicas: int,
             trace_dir: Optional[str], counter: CompileCounter):
    import jax
    from bench import generator
    from repro.core import telemetry
    vocab = cfg["model"]["vocab_size"]

    def job(router, reps, st):
        wait_for_replicas(router, reps)
        warm(reps, generator.warm_requests(mix, vocab, seed))
        hists = [telemetry.metrics().histogram(f"engine.ttft_us.{p}")
                 for p in ("direct", "chunked")]
        if mix["loop"] == "open":
            recs = [Rec(r.prompt, r.max_new, r.shared, r.due_s) for r in
                    generator.open_schedule(mix, seconds, vocab, seed)]
        else:
            callers = mix["callers_per_slot"] * cfg["engine"]["num_slots"] \
                * replicas
            streams = [[Rec(r.prompt, r.max_new, r.shared) for r in s]
                       for s in generator.closed_streams(
                           mix, callers, vocab, seed, mix["per_caller"])]
            recs = []
        e0, r0 = engine_counters(reps), router_counters(router)
        for h in hists:
            h.reset()
        # The profiler records the middle TRACE_S of the window: reading a
        # whole window's trace back would outlast a run's time limit.
        span = min(TRACE_S, seconds)
        timers = []
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            timers = [threading.Timer((seconds - span) / 2,
                                      jax.profiler.start_trace,
                                      (trace_dir,), {"profiler_options": opts}),
                      threading.Timer((seconds + span) / 2,
                                      jax.profiler.stop_trace)]

        def close():
            for t in timers:
                t.join()

        st.setup_s = time.monotonic() - T_START
        for t in timers:
            t.start()
        counter.armed = True
        if mix["loop"] == "open":
            open_loop(router, recs, seconds, mix["grace_s"], close)
        else:
            closed_loop(router, streams, seconds, recs)
            close()
        counter.armed = False
        st.engine = delta(e0, engine_counters(reps))
        st.router = delta(r0, router_counters(router))
        merged = telemetry.Histogram("engine.ttft_us")
        for h in hists:
            merged.merge(h)
        st.ttft_us = merged
        st.recs = list(recs)
        st.memory = peak_memory()

    return job


# -- device ----------------------------------------------------------------

def use_compile_cache() -> None:
    """Every executable goes to ``CACHE``, however quick its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_memory() -> int:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def reduce_trace(run: Run, trace_dir: str) -> None:
    from bench import trace
    events = trace.load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    planes = trace.device_planes(events)
    if not planes:
        return
    lo = min(e["start_ns"] for e in events)
    hi = max(e["start_ns"] + e["dur_ns"] for e in events)
    run.events = events
    run.trace_window_s = (hi - lo) * 1e-9
    run.busy_s = sum(trace.busy_ns(events, p, lo, hi)
                     for p in planes) * 1e-9 / len(planes)
    gaps = trace.idle_gaps(events, planes[0], lo, hi)
    run.breakdown = {"device_ops": trace.top_ops(events),
                     "idle_gaps": trace.label_gaps(events, gaps)}


# -- correctness ---------------------------------------------------------------

def check_sample(recs: list[Rec], n: int, seed: int) -> list[Rec]:
    """The request with the most served tokens, the one with the longest
    sequence, and others drawn from the seed, ``n`` in all."""
    import numpy as np
    done = [r for r in recs if r.ok and r.out_tokens > 0]
    if not done:
        return []
    pick = {id(max(done, key=lambda r: r.out_tokens)): None,
            id(max(done, key=lambda r: len(r.out))): None}
    rng = np.random.default_rng([int(seed) % (1 << 64), 7])
    for i in rng.permutation(len(done)):
        if len(pick) >= n:
            break
        pick.setdefault(id(done[int(i)]), None)
    by_id = {id(r): r for r in done}
    return [by_id[k] for k in pick]


def seq_len(cfg: dict, mix: dict) -> int:
    """Padded length of a checked sequence: the longest the mix can make,
    rounded up to the reference's query block."""
    sp = mix.get("system_prompts") or {}
    longest = (sp.get("tokens", 0) + max(mix["prompt_lengths"])
               + mix["max_new"]["max"])
    q = config_module(cfg, "reference").Q_BLOCK
    return -(-longest // q) * q


def logit_gaps(cfg: dict, mix: dict, params, recs: list[Rec],
               quant: bool = False) -> list[float]:
    """For each request, the widest gap between the reference's best logit
    and its logit of the token that was served (``quant``: of the token
    the float8 control puts first) at each served position."""
    import jax.numpy as jnp
    import numpy as np
    reference = config_module(cfg, "reference")
    S, M = seq_len(cfg, mix), mix["max_new"]["max"]
    out = []
    for r in recs:
        seq = np.zeros(S, np.int32)
        seq[:len(r.out)] = r.out
        P, n = r.prompt_len, r.out_tokens
        idx = np.minimum(np.arange(M) + P - 1, P + n - 2)
        ref = reference.logits_at(cfg["model"], params, jnp.asarray(seq),
                                  jnp.asarray(idx, np.int32))
        if quant:
            ctl = reference.logits_at(cfg["model"], params,
                                      jnp.asarray(seq),
                                      jnp.asarray(idx, np.int32), quant=True)
            tok = jnp.argmax(ctl, -1)
        else:
            tok = jnp.asarray(np.asarray(r.out[P:P + n]))
            tok = jnp.concatenate([tok, jnp.full((M - n,), tok[-1])])
        gap = jnp.max(ref, -1) - jnp.take_along_axis(ref, tok[:, None],
                                                      -1)[:, 0]
        out.append(float(jnp.max(gap[:n])))
    return out


def reference_params(cfg: dict, seed: int):
    return config_module(cfg, "weights").make(cfg["model"], seed,
                                              cfg["weights_dtype"])


# -- one run -------------------------------------------------------------------

def serve_window(cfg: dict, mix: dict, cell: dict, seed: int, seconds: float,
                 trace: bool, counter: CompileCounter):
    """Launch the program, set up, run one window; returns the driver's
    state (``recs``, counters, ``memory``, ``setup_s``)."""
    from repro import core as lp
    serve_seeded_weights(cfg, seed)
    trace_dir = None
    if trace:
        trace_dir = str(OUT / "trace" / cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    state = types.SimpleNamespace(error=None, recs=[], trace_dir=trace_dir)
    job = make_job(cfg, mix, seconds, seed, cell["chips"], trace_dir, counter)
    program = build_program(cfg, cell["chips"], job, state)
    launcher = lp.launch_and_wait(program, timeout_s=1800.0)
    launcher.stop()
    del launcher, program
    gc.collect()
    if state.error is not None:
        raise state.error
    return state


def main(argv=None, *, bench: Optional[dict] = None,
         mixes_dir: Optional[pathlib.Path] = None,
         require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the float8 control's tokens in place of "
                    "the served ones (must come out not correct)")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("bench/run.py: no src/repro beside bench/; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    bench = bench or load_benchmark()
    cell = find_cell(bench, args.workload)
    cfg = load_config(bench, cell["config"])
    from bench import generator
    mix = (generator.load_mix(cell["traffic"]) if mixes_dir is None else
           json.loads((mixes_dir / f"{cell['traffic']}.json").read_text()))

    use_compile_cache()
    info = device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}; compile cache {CACHE}", flush=True)
    if require_tpu and info["platform"] != "tpu":
        print("bench/run.py: no TPU found; the benchmark runs only on the "
              "chip", file=sys.stderr)
        return 1
    if info["count"] < cell["chips"]:
        print(f"bench/run.py: {cell['name']} needs {cell['chips']} chips, "
              f"found {info['count']}", file=sys.stderr)
        return 1
    peaks = peaks_for(info["kind"]) if require_tpu else {
        "bf16_flops_per_s": float("nan"), "hbm_bytes_per_s": float("nan")}

    counter = CompileCounter()
    st = serve_window(cfg, mix, cell, args.seed, args.seconds,
                      bool(args.trace), counter)
    run = Run(cell=cell, model=cfg["model"], mix=mix, chips=cell["chips"],
              num_slots=cfg["engine"]["num_slots"], peaks=peaks,
              window_s=args.seconds, setup_s=st.setup_s, requests=st.recs,
              engine=st.engine, router=st.router, ttft_us=st.ttft_us,
              matmul_params=config_module(cfg, "weights").param_count(
                  cfg["model"], matmul_only=True),
              compiles_in_window=counter.count)
    if args.trace:
        reduce_trace(run, st.trace_dir)

    failed = [r for r in run.requests if not r.ok
              and (r.due_s is not None or (r.done or 1e9) <= run.window_s)]
    attempted = len(run.requests) if mix["loop"] == "open" else \
        sum(1 for r in run.requests
            if r.done is not None and r.done <= run.window_s)
    sample = check_sample(run.requests, mix["check_requests"], args.seed)
    params = reference_params(cfg, args.seed)
    gaps = logit_gaps(cfg, mix, params, sample)
    if args.control:
        print(f"reading program logit_gap: {max(gaps, default=None)} "
              "(the control's is the one compared)", file=sys.stderr)
        gaps = logit_gaps(cfg, mix, params, sample, quant=True)
    del params
    limit = cfg["limits"]["logit_gap"]
    gap = max(gaps) if gaps else float("inf")
    served = sum(r.out_tokens for r in sample)
    correct = bool(gaps) and gap <= limit and not failed

    metrics = {}
    for m in metrics_for(bench, cell["name"], bool(args.trace)):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(info, count=info["count"], memory_peak_bytes=st.memory)
    if args.trace and run.busy_s:
        device.update(busy_s=run.busy_s, window_s=run.trace_window_s)
    result = {"correct": correct, "attempted": attempted,
              "failed": len(failed), "metrics": metrics, "device": device}
    if args.trace and run.breakdown:
        result["breakdown"] = run.breakdown
    if args.control:
        result["control"] = "float8"
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "failed_requests": {"value": len(failed), "limit": 0}}
    result["checks"] = checks

    n_due = len(run.requests)
    print(f"requests: {n_due} in the window, {attempted} attempted, "
          f"{len(failed)} failed; compiles in window {counter.count}; "
          f"checked {len(sample)} requests, {served} served tokens",
          flush=True)
    if mix["loop"] == "open":
        toks = sum(r.out_tokens for r in run.requests if r.ok)
        print(f"open loop: {toks / run.window_s:.1f} output tokens/s "
              "offered and served (set by the schedule, not a metric)",
              flush=True)
    for r in failed[:5]:
        print(f"failed request: {r.error}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
