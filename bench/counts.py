"""Counts the engine writes into a profiler trace by name.

``telemetry.count(name, n)`` puts an empty phase named ``<name>.<n>`` on the
engine thread's host plane (the trace keeps names, not annotation
arguments); ``total`` sums ``n`` over the phases of one name that the
traced stretch holds.
"""

from __future__ import annotations

from bench import trace


def total(events: list[dict], name: str) -> int | None:
    """Sum of the counts named ``name`` in the trace, None where it holds
    none (a program that writes no such count)."""
    prefix = name + "."
    vals = [int(e["name"][len(prefix):]) for e in events
            if e["plane"] == trace.HOST_PLANE and e["name"].startswith(prefix)
            and e["name"][len(prefix):].isdigit()]
    return sum(vals) if vals else None
