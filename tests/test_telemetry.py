"""Telemetry layer: mergeable histograms, cross-node trace propagation
(single trace across router -> replica hops, including failover), fabric
events, the TelemetryHub collector, and the Chrome trace export.

The fabric tests drive the real Router over the real courier inproc
transport against fake replicas (same harness as tests/test_fabric.py);
the real-engine span path runs in test_engine_spans_and_ttft.
"""

import json
import threading
import time
import uuid

import numpy as np
import pytest

from repro.core import courier, telemetry
from repro.core.discovery import Registry
from repro.core.telemetry import (Histogram, TelemetryHub, TraceContext,
                                  chrome_trace, merge_metric_snapshots,
                                  trace_coverage)
from repro.serve.router import Router


@pytest.fixture(autouse=True)
def clean_buffers():
    """Spans/events land in process-global rings; start every test from
    an empty one so assertions only see their own records."""
    telemetry.spans_buffer().drain()
    telemetry.events_buffer().drain()
    yield
    telemetry.spans_buffer().drain()
    telemetry.events_buffer().drain()


# ---- histograms --------------------------------------------------------------

def _percentile_tolerance():
    # Bucket midpoints sit within (1 + 1/16) of the bucket edges; the
    # worst-case relative error against the exact nearest-rank value is
    # ~6.7%. Assert with headroom.
    return 0.10


def test_histogram_exact_count_sum_min_max():
    h = Histogram("x")
    vals = [3.0, 1.5, 0.25, 1000.0, 7.0]
    for v in vals:
        h.record(v)
    assert h.count == len(vals)
    assert h.total == pytest.approx(sum(vals))
    assert h.mean == pytest.approx(np.mean(vals))
    assert h.vmin == min(vals) and h.vmax == max(vals)
    # Percentiles are clamped to the observed range.
    assert h.percentile(0) >= h.vmin
    assert h.percentile(100) <= h.vmax


def test_histogram_nonpositive_values_bucket_zero():
    h = Histogram("x")
    h.record(0.0)
    h.record(-5.0)
    assert h.count == 2 and h.counts[0] == 2
    assert -5.0 <= h.percentile(50) <= 0.0      # clamped to observed range


def test_histogram_snapshot_roundtrip():
    h = Histogram("x")
    for v in [1e-6, 0.5, 2.0, 3e9]:
        h.record(v)
    back = Histogram.from_snapshot("x", h.snapshot())
    np.testing.assert_array_equal(back.counts, h.counts)
    assert back.count == h.count and back.total == h.total
    assert back.vmin == h.vmin and back.vmax == h.vmax
    assert back.percentile(95) == h.percentile(95)


def test_empty_histogram_is_safe():
    h = Histogram("x")
    assert h.percentile(50) == 0.0 and h.mean == 0.0
    snap = h.snapshot()
    assert snap["count"] == 0 and snap["buckets"] == {}
    assert Histogram.from_snapshot("x", snap).count == 0


try:
    from hypothesis import given, settings, strategies as st

    values = st.lists(st.floats(min_value=1e-6, max_value=1e9,
                                allow_nan=False, allow_infinity=False),
                      min_size=1, max_size=200)

    @given(values, values)
    @settings(max_examples=50, deadline=None)
    def test_histogram_merge_equals_union(a, b):
        """merge(A, B) must be indistinguishable from recording A + B
        into one histogram — the property the collector's roll-up rests
        on."""
        ha, hb, hu = Histogram("a"), Histogram("b"), Histogram("u")
        for v in a:
            ha.record(v)
        for v in b:
            hb.record(v)
        for v in a + b:
            hu.record(v)
        ha.merge(hb)
        np.testing.assert_array_equal(ha.counts, hu.counts)
        assert ha.count == hu.count
        assert ha.total == pytest.approx(hu.total)
        assert ha.vmin == hu.vmin and ha.vmax == hu.vmax
        for q in (50, 95, 99):
            assert ha.percentile(q) == hu.percentile(q)

    @given(values, st.sampled_from([50, 90, 95, 99]))
    @settings(max_examples=50, deadline=None)
    def test_histogram_percentile_within_bucket_error(vals, q):
        """The log2/8-sub-bucket geometry bounds percentile error: the
        reported value is the midpoint of the bucket holding the exact
        nearest-rank sample, so it lands within ~7% of it."""
        h = Histogram("x")
        for v in vals:
            h.record(v)
        exact = sorted(vals)[max(1, int(np.ceil(len(vals) * q / 100.0))) - 1]
        got = h.percentile(q)
        tol = _percentile_tolerance()
        assert got == pytest.approx(exact, rel=tol) or (
            min(vals) <= got <= max(vals)
            and abs(got - exact) <= tol * max(exact, got))
except ImportError:  # pragma: no cover - hypothesis is in the image
    pass


def test_merge_metric_snapshots():
    h1, h2 = Histogram("lat"), Histogram("lat")
    for v in (1.0, 2.0):
        h1.record(v)
    for v in (100.0, 200.0):
        h2.record(v)
    merged = merge_metric_snapshots([
        {"counters": {"reqs": 3}, "gauges": {"depth": 1.0},
         "histograms": {"lat": h1.snapshot()}},
        {"counters": {"reqs": 4, "errs": 1}, "gauges": {"depth": 7.0},
         "histograms": {"lat": h2.snapshot()}},
    ])
    assert merged["counters"] == {"reqs": 7, "errs": 1}
    assert merged["gauges"]["depth"] == 7.0       # last write wins
    lat = merged["histograms"]["lat"]
    assert lat["count"] == 4
    assert lat["mean"] == pytest.approx((1 + 2 + 100 + 200) / 4)
    assert "p50" in lat and "p95" in lat and "p99" in lat


def test_metrics_registry_reset_and_snapshot():
    reg = telemetry.MetricsRegistry()
    reg.counter("c").inc(5)
    reg.gauge("g").set(2.5)
    reg.histogram("h").record(10.0)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 2.5
    assert snap["histograms"]["h"]["count"] == 1
    reg.reset()
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 0
    assert snap["histograms"]["h"]["count"] == 0


# ---- trace context & spans ---------------------------------------------------

def test_trace_context_wire_roundtrip():
    ctx = telemetry.start_trace()
    back = TraceContext.from_wire(ctx.to_wire())
    assert back == ctx
    assert TraceContext.from_wire("garbage") is None
    child = ctx.child("abc")
    assert child.trace_id == ctx.trace_id and child.parent_id == "abc"


def test_inject_extract_and_idempotency():
    ctx = telemetry.start_trace()
    with telemetry.activate(ctx):
        kwargs = telemetry.inject({"max_new": 4})
        assert telemetry.TRACE_KEY in kwargs
        # Injection never overwrites an explicitly pre-parented envelope.
        pre = dict(kwargs)
        assert telemetry.inject(pre)[telemetry.TRACE_KEY] \
            == kwargs[telemetry.TRACE_KEY]
    got = telemetry.extract(kwargs)
    assert got == ctx and telemetry.TRACE_KEY not in kwargs
    # Unsampled contexts do not propagate.
    with telemetry.activate(telemetry.start_trace(sampled=False)):
        assert telemetry.TRACE_KEY not in telemetry.inject({})


def test_span_nesting_parents_correctly():
    ctx = telemetry.start_trace()
    with telemetry.activate(ctx):
        with telemetry.span("outer"):
            with telemetry.span("inner", k=3):
                pass
    spans = {s["name"]: s for s in telemetry.spans_buffer().drain()}
    assert spans["inner"]["parent"] == spans["outer"]["id"]
    assert spans["outer"]["parent"] is None
    assert spans["inner"]["attrs"]["k"] == 3
    assert spans["outer"]["trace"] == ctx.trace_id


def test_unsampled_span_records_nothing():
    with telemetry.activate(telemetry.start_trace(sampled=False)):
        with telemetry.span("quiet"):
            pass
    assert telemetry.spans_buffer().drain() == []


def test_span_buffer_is_bounded():
    buf = telemetry.SpanBuffer(maxlen=4)
    for i in range(10):
        buf.append({"i": i})
    drained = buf.drain()
    assert [d["i"] for d in drained] == [6, 7, 8, 9]
    assert buf.drain() == []


# ---- cross-node propagation through the fabric -------------------------------

class TracedReplica:
    """EngineServer-shaped fake that records engine-style spans under
    whatever trace context the transport delivered."""

    def __init__(self, fail=False):
        self.fail = fail
        self.calls = 0

    def generate(self, prompt, max_new=None):
        self.calls += 1
        if self.fail:
            raise RuntimeError("engine stopped")
        with telemetry.span("admission"):
            pass
        with telemetry.span("prefill", tokens=len(prompt)):
            time.sleep(0.001)
        with telemetry.span("decode"):
            time.sleep(0.001)
        return np.concatenate([np.asarray(prompt, np.int32), [7]])

    def load(self):
        return {"num_slots": 8, "free_slots": 8, "queue_depth": 0,
                "ewma_us_per_token": 100.0}

    def health(self):
        return {"status": "ok"}

    def telemetry(self):
        return telemetry.telemetry_snapshot(service=self.load())


@pytest.fixture
def fabric():
    registry = Registry(ttl_s=5.0)
    names = []

    def add(replica, load=None, name=None):
        name = name or f"tel-{uuid.uuid4().hex[:8]}"
        courier.inprocess.register(name, replica)
        names.append(name)
        registry.register(name, f"inproc://{name}",
                          load if load is not None else replica.load())
        return name

    yield registry, add
    for name in names:
        courier.inprocess.unregister(name)


def _traced_submit(router, prompt):
    """Client-side half of a sampled request: mint the trace, run submit
    under a context parented on a pre-minted root span id, then record
    the root 'request' span over the measured e2e window."""
    ctx = telemetry.start_trace()
    root_sid = telemetry.new_span_id()
    t0w, t0 = time.time(), time.perf_counter()
    with telemetry.activate(ctx.child(root_sid)):
        out = router.submit(prompt)
    dur = time.perf_counter() - t0
    telemetry.record_span("request", ctx, t0w, dur, span_id=root_sid,
                          root=True)
    return out, ctx, root_sid, t0w, dur


@pytest.mark.parametrize("coalesce", [True, False])
def test_sampled_request_yields_single_nested_trace(fabric, coalesce):
    """One sampled request through a 2-replica fabric produces ONE trace
    whose spans nest correctly across the router -> replica hop."""
    registry, add = fabric
    add(TracedReplica())
    add(TracedReplica())
    with Router(registry, refresh_s=0.05, startup_wait_s=2.0,
                coalesce=coalesce) as router:
        out, ctx, root_sid, _, _ = _traced_submit(
            router, np.arange(4, dtype=np.int32))
    assert out[-1] == 7
    spans = telemetry.spans_buffer().drain()
    assert spans and {s["trace"] for s in spans} == {ctx.trace_id}
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # Router-side spans hang off the client's root span.
    (queue,) = by_name["queue"]
    (dispatch,) = by_name["dispatch"]
    (reply,) = by_name["reply"]
    assert queue["parent"] == root_sid
    assert dispatch["parent"] == root_sid
    assert reply["parent"] == root_sid
    # Replica-side spans nest under the dispatch that carried them.
    for name in ("admission", "prefill", "decode"):
        (s,) = by_name[name]
        assert s["parent"] == dispatch["id"], name
    (root,) = by_name["request"]
    assert root["id"] == root_sid and root["attrs"]["root"] is True


def test_failover_hops_stay_in_one_trace(fabric):
    """A replica dying mid-request adds a second queue/dispatch hop to
    the SAME trace; replica-side spans only hang off the surviving
    dispatch."""
    registry, add = fabric
    # The failing replica advertises the better load -> picked first.
    add(TracedReplica(fail=True),
        load={"num_slots": 8, "free_slots": 8, "queue_depth": 0})
    live = TracedReplica()
    add(live, load={"num_slots": 8, "free_slots": 2, "queue_depth": 3})
    with Router(registry, refresh_s=0.05, startup_wait_s=2.0) as router:
        out, ctx, root_sid, t0w, dur = _traced_submit(
            router, np.arange(4, dtype=np.int32))
    assert out[-1] == 7 and live.calls == 1
    spans = telemetry.spans_buffer().drain()
    assert {s["trace"] for s in spans} == {ctx.trace_id}      # single trace
    queues = [s for s in spans if s["name"] == "queue"]
    dispatches = [s for s in spans if s["name"] == "dispatch"]
    assert len(queues) == 2 and len(dispatches) == 2          # failover hop
    assert {q["attrs"]["attempt"] for q in queues} == {1, 2}
    live_dispatch = [d for d in dispatches
                    if any(s["parent"] == d["id"] for s in spans
                           if s["name"] == "decode")]
    assert len(live_dispatch) == 1
    # The trace explains (almost) every microsecond of the e2e window:
    # fake replicas do ~no work outside their spans, so the union of
    # non-root spans must cover most of it.
    cov = trace_coverage(spans, ctx.trace_id, t0w, dur)
    assert cov > 0.5
    # The drop left a queryable fabric event with a cause.
    events = telemetry.events_buffer().drain()
    kinds = {e["kind"] for e in events}
    assert "replica_dropped" in kinds and "eviction" in kinds
    assert all(e["cause"] for e in events if e["kind"] == "eviction")


def test_router_telemetry_rpc_surfaces_transport_stats(fabric):
    registry, add = fabric
    add(TracedReplica())
    with Router(registry, refresh_s=0.05, startup_wait_s=2.0) as router:
        assert router.submit(np.arange(3, dtype=np.int32))[-1] == 7
        snap = router.telemetry()
    assert "metrics" in snap and "pid" in snap
    transports = snap["service"]["transports"]
    assert transports, "replica transport counters missing"
    (io,) = transports.values()
    assert io["calls"] + io["batched_calls_in_frames"] >= 1


# ---- real engine spans -------------------------------------------------------

def test_engine_spans_and_ttft():
    """A sampled request through the real ServeEngine yields admission /
    prefill / decode spans and a TTFT histogram sample."""
    import jax
    from repro import configs
    from repro.models import transformer
    from repro.serve.engine import ServeEngine

    cfg = configs.get_reduced("qwen2-1.5b")
    params = transformer.init_params(cfg, jax.random.key(0))
    engine = ServeEngine(cfg, params, num_slots=2, context_len=24,
                         max_new=4)
    ctx = telemetry.start_trace()
    with telemetry.activate(ctx):
        fut = engine.submit(np.arange(5, dtype=np.int32) % cfg.vocab_size)
    steps = 0
    while not fut.done():
        engine.step()
        steps += 1
        assert steps < 500
    assert fut.result().shape == (9,)
    spans = [s for s in telemetry.spans_buffer().drain()
             if s["trace"] == ctx.trace_id]
    names = {s["name"] for s in spans}
    assert {"admission", "prefill", "decode"} <= names
    hists = telemetry.metrics().snapshot()["histograms"]
    ttft = [k for k in hists if k.startswith("engine.ttft_us.")]
    assert ttft and any(hists[k]["count"] >= 1 for k in ttft)


def _tiny_engine(**kw):
    import jax
    from repro import configs
    from repro.models import transformer
    from repro.serve.engine import ServeEngine

    cfg = configs.get_reduced("qwen2-1.5b")
    params = transformer.init_params(cfg, jax.random.key(0))
    return cfg, ServeEngine(cfg, params, num_slots=2, context_len=24,
                            max_new=4, **kw)


def test_engine_phases_land_in_the_profiler_trace(tmp_path):
    """A paged engine's loop served under ``jax.profiler``: its phases are
    host events of the trace, read back by the benchmark's loader."""
    import re
    import jax
    from bench import trace

    cfg, engine = _tiny_engine(page_size=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with engine:
            fut = engine.submit(np.arange(5, dtype=np.int32) % cfg.vocab_size)
            assert fut.result(timeout=120).shape == (9,)
            time.sleep(0.15)                    # at least one whole wait
    finally:
        jax.profiler.stop_trace()
    names = {e["name"] for e in trace.load(str(tmp_path))
             if e["plane"] == trace.HOST_PLANE}
    assert {"engine.prefill", "engine.idle", "engine.admit"} <= names
    assert any(re.fullmatch(r"engine\.window\.k\d+", n) for n in names)


def test_phase_records_nothing_without_a_session(tmp_path):
    """A phase outside a profiler session only times itself; inside one it
    is a host event of the trace, as is a sampled span."""
    import jax
    from bench import trace

    with telemetry.phase("outside") as ph:
        time.sleep(0.002)
    assert ph.dur >= 0.002 and ph.wall <= time.time()
    assert telemetry.spans_buffer().drain() == []
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.phase("inside"):
            pass
        with telemetry.activate(telemetry.start_trace()):
            with telemetry.span("sampled"):
                pass
    finally:
        jax.profiler.stop_trace()
    names = {e["name"] for e in trace.load(str(tmp_path))}
    assert {"inside", "sampled"} <= names and "outside" not in names


def test_sampled_spans_take_their_times_from_the_phases(monkeypatch):
    """A sampled request's ``decode`` spans are its windows' phases and its
    ``prefill`` span the prefill phase: one timing, not two."""
    timed = []

    class Recorded(telemetry.phase):
        __slots__ = ()

        def __exit__(self, *exc_info):
            super().__exit__(*exc_info)
            timed.append(self)

    monkeypatch.setattr(telemetry, "phase", Recorded)
    cfg, engine = _tiny_engine()
    ctx = telemetry.start_trace()
    with telemetry.activate(ctx):
        fut = engine.submit(np.arange(5, dtype=np.int32) % cfg.vocab_size)
    while not fut.done():
        engine.step()
    spans = [s for s in telemetry.spans_buffer().drain()
             if s["trace"] == ctx.trace_id]
    windows = [(p.wall, p.dur) for p in timed
               if p.name.startswith("engine.window.k")]
    decode = [(s["ts"], s["dur"]) for s in spans if s["name"] == "decode"]
    assert decode and decode == windows
    (prefill,) = [p for p in timed if p.name == "engine.prefill"]
    (span,) = [s for s in spans if s["name"] == "prefill"]
    assert (span["ts"], span["dur"]) == (prefill.wall, prefill.dur)


# ---- collector ---------------------------------------------------------------

class FakeNode:
    """telemetry()-shaped scrape target with a controllable pid."""

    def __init__(self, node, pid, counters=None, spans=(), events=()):
        self._snap = {"node": node, "pid": pid, "time": time.time(),
                      "metrics": {"counters": dict(counters or {}),
                                  "gauges": {}, "histograms": {}},
                      "spans": list(spans), "events": list(events)}
        self.scrapes = 0

    def telemetry(self):
        self.scrapes += 1
        snap = dict(self._snap)
        # Spans drain: only the first scrape carries them.
        if self.scrapes > 1:
            snap["spans"], snap["events"] = [], []
        return snap


def _span(trace, sid, parent, name, ts, dur, node="n"):
    return {"name": name, "trace": trace, "id": sid, "parent": parent,
            "node": node, "ts": ts, "dur": dur, "attrs": {}}


def test_hub_merges_per_pid_and_accumulates_spans(tmp_path):
    sp = _span("t1", "s1", None, "request", 100.0, 1.0)
    a = FakeNode("a", pid=1, counters={"reqs": 5}, spans=[sp],
                 events=[{"kind": "swap", "cause": "v2", "node": "a",
                          "ts": 100.5, "attrs": {}}])
    # Same pid as a (thread-launched sibling sharing the registry): its
    # counters must NOT double the merge.
    b = FakeNode("b", pid=1, counters={"reqs": 5})
    c = FakeNode("c", pid=2, counters={"reqs": 2})
    hub = TelemetryHub(targets=[a, b, c], out_dir=str(tmp_path))
    assert hub.scrape_once() == 3
    assert hub.scrape_once() == 3                  # spans don't duplicate
    merged = hub.merged_metrics()
    assert merged["counters"]["reqs"] == 7         # 5 (pid 1, once) + 2
    assert len(hub.spans()) == 1
    assert hub.events()[0]["kind"] == "swap"
    # Export: merged snapshot + Perfetto-loadable trace.
    snap = json.loads((tmp_path / "telemetry.json").read_text())
    assert snap["merged"]["counters"]["reqs"] == 7
    assert snap["hub"]["scrapes"] >= 3
    trace = json.loads((tmp_path / "trace.json").read_text())
    evs = trace["traceEvents"]
    assert any(e["ph"] == "X" and e["name"] == "request" for e in evs)
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    assert any(e["ph"] == "i" and "swap" in e["name"] for e in evs)


def test_hub_scrapes_registry_replicas(fabric):
    registry, add = fabric
    rep = TracedReplica()
    add(rep)
    hub = TelemetryHub(registry=registry)
    assert hub.scrape_once() >= 1
    # The replica's process registry reached the hub (pid-keyed).
    assert hub.snapshot()["hub"]["scrapes"] >= 1
    hub.close()


def test_hub_survives_dead_targets():
    class Dead:
        def telemetry(self):
            raise ConnectionError("gone")

    hub = TelemetryHub(targets=[Dead(), FakeNode("ok", pid=9)])
    assert hub.scrape_once() == 1
    assert hub.snapshot()["hub"]["scrape_errors"] == 1


# ---- chrome trace & coverage -------------------------------------------------

def test_chrome_trace_maps_nodes_to_pids_and_traces_to_tids():
    spans = [_span("t1", "s1", None, "a", 1.0, 0.5, node="router"),
             _span("t1", "s2", "s1", "b", 1.1, 0.2, node="engine"),
             _span("t2", "s3", None, "a", 2.0, 0.1, node="router")]
    out = chrome_trace(spans)
    evs = [e for e in out["traceEvents"] if e["ph"] == "X"]
    pids = {e["args"]["trace"]: e["tid"] for e in evs}
    assert pids["t1"] != pids["t2"]               # traces on separate rows
    nodes = {e["pid"] for e in evs}
    assert len(nodes) == 2                        # router + engine
    json.dumps(out)                               # serializable as-is


def test_trace_coverage_unions_overlaps_and_skips_root():
    spans = [
        _span("t", "root", None, "request", 0.0, 10.0),
        _span("t", "a", "root", "queue", 0.0, 4.0),
        _span("t", "b", "root", "dispatch", 3.0, 4.0),   # overlaps a
        _span("t", "c", "root", "decode", 8.0, 1.0),
        _span("other", "x", None, "noise", 0.0, 10.0),
    ]
    spans[0]["attrs"]["root"] = True
    cov = trace_coverage(spans, "t", 0.0, 10.0)
    assert cov == pytest.approx(0.8)              # [0,7) + [8,9) = 8 of 10
    assert trace_coverage(spans, "t", 0.0, 0.0) == 0.0


# ---- structured logging ------------------------------------------------------

def test_node_logger_prefixes_and_records_events(capsys):
    log = telemetry.get_logger("worker-3")
    log.info("starting", step=7)
    log.error("boom", reason="test")
    err = capsys.readouterr().err
    assert "[worker-3] INFO: starting (step=7)" in err
    assert "[worker-3] ERROR: boom" in err
    events = telemetry.events_buffer().drain()
    assert [e["kind"] for e in events] == ["error"]
    assert events[0]["cause"] == "boom" and events[0]["node"] == "worker-3"


def test_node_logger_exception_appends_traceback(capsys):
    log = telemetry.get_logger("w")
    try:
        raise ValueError("kaput")
    except ValueError:
        log.exception("worker crashed")
    err = capsys.readouterr().err
    assert "worker crashed" in err and "ValueError: kaput" in err
    (event,) = telemetry.events_buffer().drain()
    assert event["kind"] == "error"


# ---- hot-path sanity ---------------------------------------------------------

def test_unsampled_hot_path_is_cheap():
    """No trace context active: inject is a dict passthrough and span a
    no-op, so unsampled requests pay next to nothing."""
    kwargs = {"max_new": 4}
    assert telemetry.inject(kwargs) is kwargs
    h = telemetry.metrics().histogram("bench.sanity")
    t0 = time.perf_counter()
    n = 20000
    for _ in range(n):
        h.record(12.5)
    per_record = (time.perf_counter() - t0) / n
    assert per_record < 50e-6      # generous: just catches O(n) mistakes


def test_concurrent_recording_is_safe():
    h = telemetry.metrics().histogram("concurrent.h")
    c = telemetry.metrics().counter("concurrent.c")

    def work():
        for _ in range(1000):
            h.record(3.0)
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == int(h.counts.sum())
    assert c.value <= 4000 and c.value > 0
