"""From a profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a flat
list of events ``{"plane", "line", "name", "start_ns", "dur_ns"}``; the
reductions below take such a list, so the tests run them on a small
recorded trace (``tests/data/trace_events.json``) without a chip.

Device planes are named ``/device:TPU:<n>``. On each, the ``XLA Ops`` line
holds one event per operation that ran; busy time is the union of those
intervals, so overlapping operations count once.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# Control-flow operations span the operations of their bodies.
CONTAINER = re.compile(r"^%?(?:while|conditional|call)[.\s]")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def load(trace_dir: str) -> list[dict]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns})
    return out


def device_planes(events: list[dict]) -> list[str]:
    return sorted({e["plane"] for e in events
                   if DEVICE_PLANE.match(e["plane"])})


def op_events(events: list[dict], plane: str) -> list[dict]:
    return [e for e in events if e["plane"] == plane
            and e["line"] == OPS_LINE]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint ones, in order."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: list[dict], plane: str, lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi)`` in which some operation ran on ``plane``."""
    spans = [(max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi))
             for e in op_events(events, plane)]
    return sum(e - s for s, e in union([p for p in spans if p[1] > p[0]]))


def idle_gaps(events: list[dict], plane: str, lo: float,
              hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi)`` in which no operation ran."""
    spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"])
             for e in op_events(events, plane)]
    gaps, t = [], lo
    for s, e in union(spans):
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [g for g in gaps if g[1] > g[0]]


def kernel_ns(events: list[dict], pattern: str, plane: str | None = None,
              lo: float = float("-inf"), hi: float = float("inf")) -> float:
    """Summed device time of the operations whose name matches ``pattern``
    (a regular expression), on one device plane or all of them."""
    rx = re.compile(pattern)
    planes = [plane] if plane else device_planes(events)
    return float(sum(e["dur_ns"] for p in planes for e in op_events(events, p)
                     if rx.search(e["name"]) and lo <= e["start_ns"] < hi))


def op_name(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``%fusion.3``."""
    return name.split(" = ")[0]


def top_ops(events: list[dict], n: int = 10) -> list[list]:
    """The ``n`` operations that took the most device time, with their
    seconds summed over every device plane; loops and calls are left out,
    since their bodies' operations are counted."""
    tot: dict[str, float] = {}
    for p in device_planes(events):
        for e in op_events(events, p):
            if not CONTAINER.match(e["name"]):
                k = op_name(e["name"])
                tot[k] = tot.get(k, 0.0) + e["dur_ns"]
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]


def label_gaps(events: list[dict], gaps: list[tuple[float, float]],
               n: int = 10) -> list[list]:
    """The ``n`` longest gaps, each named by the innermost host event that
    covers at least half of it (``"host idle"`` where none does), with
    its seconds."""
    host = [e for e in events if e["plane"] == HOST_PLANE]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, best_dur = "host idle", float("inf")
        for h in host:
            ov = min(e, h["start_ns"] + h["dur_ns"]) - max(s, h["start_ns"])
            if 2 * ov >= e - s and h["dur_ns"] < best_dur:
                best, best_dur = h["name"], h["dur_ns"]
        out.append([best, (e - s) * 1e-9])
    return out


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bw: float) -> tuple[float, str]:
    """Share (%) of the roofline reached in ``seconds``: the least time
    the chip needs, the larger of ``flops / peak_flops`` and ``nbytes /
    peak_bw``, over the time taken; and which of the two bounds it."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bw
    bound = "memory" if t_m >= t_c else "compute"
    return 100.0 * max(t_c, t_m) / seconds, bound
