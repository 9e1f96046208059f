"""The readers of the serve engine's phases, on hand-built traces."""

import json
import pathlib
import types

import pytest

from bench import trace
from bench.metrics import (decode_step_device_ms, device_idle,
                           device_idle_with_work, engine_token_gap_ms)

DATA = pathlib.Path(__file__).parent / "data"
DEV = "/device:TPU:0"
READERS = [device_idle_with_work, engine_token_gap_ms, decode_step_device_ms]


def _op(start, end, plane=DEV):
    return {"plane": plane, "line": trace.OPS_LINE, "name": "%fusion.1",
            "start_ns": start, "dur_ns": end - start}


def _phase(name, start, end):
    return {"plane": trace.HOST_PLANE, "line": "python", "name": name,
            "start_ns": start, "dur_ns": end - start}


def _run(events):
    lo = min(e["start_ns"] for e in events)
    hi = max(e["start_ns"] + e["dur_ns"] for e in events)
    planes = trace.device_planes(events)
    busy = sum(trace.busy_ns(events, p, lo, hi) for p in planes)
    return types.SimpleNamespace(events=events,
                                 trace_window_s=(hi - lo) * 1e-9,
                                 busy_s=busy * 1e-9 / max(len(planes), 1))


def test_idle_gap_is_split_by_the_engines_wait():
    ev = [_op(0, 100), _op(400, 500),
          _phase("engine.window.k1", 0, 100),
          _phase("engine.idle", 150, 300),       # waiting for work
          _phase("engine.admit", 300, 390)]
    run = _run(ev)
    assert device_idle.read(run) == pytest.approx(60.0)
    # Of the 300-ns gap, 150 fall in the wait: 150 of 500 had work.
    assert device_idle_with_work.read(run) == pytest.approx(30.0)


def test_token_gap_is_broken_by_a_wait():
    ev = [_op(0, 1), _phase("engine.window.k2", 0, 10_000),
          _phase("engine.window.k4", 20_000, 30_000),
          _phase("engine.admit", 31_000, 38_000),
          _phase("engine.window.k4", 40_000, 50_000),
          _phase("engine.idle", 55_000, 80_000),
          _phase("engine.window.k8", 90_000, 100_000),
          _phase("engine.window.k8", 110_000, 120_000)]
    # Pairs with no wait between them: 20 us for 4 steps, 20 us for 4,
    # 20 us for 8; the pair across the wait is left out.
    got = engine_token_gap_ms.read(_run(ev))
    assert got == pytest.approx(60_000 * 1e-6 / 16)


def test_device_time_is_clipped_to_the_windows():
    ev = [_op(5, 15), _op(18, 30), _op(25, 26), _op(40, 50),
          _phase("engine.window.k2", 10, 20),
          _phase("engine.window.k2", 22, 28),
          _phase("engine.prefill", 40, 50)]
    # Busy [5, 15] and [18, 30]; inside the windows 5 + 2 + 6 ns.
    assert decode_step_device_ms.read(_run(ev)) == pytest.approx(13e-6 / 4)


@pytest.mark.parametrize("events", [
    pytest.param([_op(0, 10), _op(20, 30)], id="no-phases"),
    pytest.param([_op(0, 10), _op(5, 30, plane="/device:TPU:1"),
                  _phase("engine.window.k1", 0, 10),
                  _phase("engine.window.k1", 12, 30)], id="two-planes"),
    pytest.param(json.loads((DATA / "trace_events.json").read_text()),
                 id="recorded-without-phases"),
])
@pytest.mark.parametrize("metric", READERS,
                         ids=lambda m: m.__name__.split(".")[-1])
def test_nothing_to_read(events, metric):
    assert metric.read(_run(events)) is None


@pytest.mark.parametrize("metric", READERS,
                         ids=lambda m: m.__name__.split(".")[-1])
def test_untraced_run_reads_nothing(metric):
    assert metric.read(types.SimpleNamespace(events=[],
                                             trace_window_s=0.0)) is None
