"""Trace reduction and the operation and byte counts, on the CPU."""

import json
import pathlib

import pytest

from bench import flops, trace, weights
from bench.metrics.decode_attn_roofline import KERNELS

DATA = pathlib.Path(__file__).parent / "data"
CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"
DEV = "/device:TPU:0"


def _ev(name, start, dur, plane=DEV, line=trace.OPS_LINE):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


def test_busy_is_a_union_and_idle_the_rest():
    ev = [_ev("a", 0, 100), _ev("b", 50, 100),       # overlap counts once
          _ev("c", 300, 100), _ev("x", 0, 1000, line="XLA Modules")]
    assert trace.busy_ns(ev, DEV, 0, 500) == 250
    assert trace.idle_gaps(ev, DEV, 0, 500) == [(150, 300), (400, 500)]
    assert trace.busy_ns(ev, DEV, 60, 320) == 90 + 20


def test_kernel_time_by_stable_name():
    ev = [_ev("%_decode_pallas.3 = bf16[8] custom-call(%a)", 0, 40),
          _ev("%fusion.1 = bf16[8] fusion(%_decode_pallas.3)", 40, 60),
          _ev("%_paged_decode_pallas.1 = bf16[8] custom-call()", 100, 10),
          _ev("%while.2 = (s32[]) while(%t)", 0, 200),
          _ev("%_decode_pallas.3 = bf16[8] custom-call(%a)", 0, 7,
              plane="/device:TPU:1")]
    assert trace.kernel_ns(ev, KERNELS) == 57
    assert trace.kernel_ns(ev, KERNELS, plane=DEV) == 50
    assert trace.top_ops(ev, 1) == [["%fusion.1", pytest.approx(60e-9)]]


def test_gaps_named_by_the_innermost_host_event():
    ev = [_ev("op", 0, 10), _ev("op", 110, 10),
          _ev("thread", 0, 1000, plane=trace.HOST_PLANE, line="t"),
          _ev("PjitFunction(prefill)", 12, 95, plane=trace.HOST_PLANE,
              line="t")]
    gaps = trace.idle_gaps(ev, DEV, 0, 120)
    assert trace.label_gaps(ev, gaps) == [
        ["PjitFunction(prefill)", pytest.approx(100e-9)]]


def test_roofline_share_and_its_bound():
    share, bound = trace.roofline_share(1e12, 819e9, 2.0, 197e12, 819e9)
    assert bound == "memory" and share == pytest.approx(50.0)
    share, bound = trace.roofline_share(197e12, 1e9, 4.0, 197e12, 819e9)
    assert bound == "compute" and share == pytest.approx(25.0)


def test_recorded_trace():
    """30 ms of a traced qwen2-1.5b.batch window on a TPU v5e."""
    ev = json.loads((DATA / "trace_events.json").read_text())
    planes = trace.device_planes(ev)
    assert planes == [DEV]
    lo = min(e["start_ns"] for e in ev)
    hi = max(e["start_ns"] + e["dur_ns"] for e in ev)
    busy = trace.busy_ns(ev, DEV, lo, hi)
    idle = sum(b - a for a, b in trace.idle_gaps(ev, DEV, lo, hi))
    assert busy > 0 and busy + idle == pytest.approx(hi - lo)
    ops = sum(e["dur_ns"] for e in trace.op_events(ev, DEV))
    assert busy <= ops                     # overlapping ops count once
    kern = trace.kernel_ns(ev, KERNELS)
    assert 0 < kern < busy


QWEN = json.loads((CONFIGS / "qwen2-1.5b.json").read_text())["model"]
STAR = json.loads((CONFIGS / "starcoder2-3b.json").read_text())["model"]


def test_counts_by_hand_at_qwen2_widths():
    per_layer = (1536 * 1536 * 2 + 1536 * 256 * 2 + 3 * 1536 * 8960)
    assert per_layer == 46_792_704
    head = 151936 * 1536
    mm = weights.param_count(QWEN, matmul_only=True)
    assert mm == 28 * per_layer + head == 1_543_569_408
    # one token at position 0 sees one key in each of 28 x 12 heads
    assert flops.sequence_flops(QWEN, mm, 0, 1) == \
        2 * 1_543_569_408 + 4 * 28 * 12 * 128
    # a decode step at position 999 reads 1000 keys and values per layer
    assert flops.decode_attn_bytes(QWEN, 999, 1000) == \
        28 * (2 * 2 * 128 * 2 * 1000 + 2 * 12 * 128 * 2)
    assert flops.request_flops(QWEN, mm, 10, 3) == \
        flops.sequence_flops(QWEN, mm, 0, 12)


def test_window_caps_the_keys_seen():
    assert flops.keys_seen(STAR, 5000) == 4096
    assert flops.keys_seen(STAR, 10) == 11
    assert flops.decode_attn_flops(STAR, 4095, 4097) == \
        4 * 30 * 24 * 128 * (4096 + 4096)
    total = sum(flops.keys_seen(STAR, t) for t in range(4000, 4200))
    assert flops._keys_sum(STAR, 4000, 4200) == total


def test_all_weights_counted():
    n = weights.param_count(QWEN)
    assert 1.5e9 < n < 1.6e9
    assert 3.0e9 < weights.param_count(STAR) < 3.1e9
