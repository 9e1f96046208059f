"""The serve engine's phases in a profiler trace, beside the device's
operations.

The engine thread (``serve/engine.py``) writes its phases into the trace
with ``telemetry.phase``: ``engine.idle`` (one wait for work),
``engine.admit`` (one admission pass, holding ``engine.prefill`` and
``engine.prefill_chunk``) and ``engine.window.k<K>`` (one fused decode
window of K steps, from its feed operands to its token pull). They land on
the host plane, on the clock of the device's operations, and the trace
keeps their names and times only: hence K in the name.

A run is read only where its trace holds engine phases and exactly one
device plane: the phases do not say yet which replica they belong to.
"""

from __future__ import annotations

import re

from bench import trace

IDLE = "engine.idle"
WINDOW = re.compile(r"^engine\.window\.k(\d+)$")


def device_plane(events: list[dict]) -> str | None:
    """The one device plane of a run whose engine wrote phases, else
    None."""
    planes = trace.device_planes(events)
    if len(planes) != 1 or not any(
            e["plane"] == trace.HOST_PLANE and e["name"].startswith("engine.")
            for e in events):
        return None
    return planes[0]


def bounds(events: list[dict]) -> tuple[float, float]:
    """The traced window, as ``bench/run.py`` takes it: first start to
    last end of any event."""
    return (min(e["start_ns"] for e in events),
            max(e["start_ns"] + e["dur_ns"] for e in events))


def intervals(events: list[dict], name: str) -> list[tuple[float, float]]:
    """``(start, end)`` of the host events named ``name``, in order."""
    return sorted((e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
                  if e["plane"] == trace.HOST_PLANE and e["name"] == name)


def windows(events: list[dict]) -> list[tuple[float, float, int]]:
    """``(start, end, K)`` of each decode window, in order."""
    out = []
    for e in events:
        m = WINDOW.match(e["name"])
        if m and e["plane"] == trace.HOST_PLANE:
            out.append((e["start_ns"], e["start_ns"] + e["dur_ns"],
                        int(m.group(1))))
    return sorted(out)


def overlap_ns(a: list[tuple[float, float]],
               b: list[tuple[float, float]]) -> float:
    """Nanoseconds shared by two lists of disjoint intervals, each in
    order."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out
