"""Share (%) of the traced window in which no operation ran on the device
while the engine thread was not waiting for work (outside its
``engine.idle`` phases). At most ``device_idle``: the difference is idle
time for want of requests."""

from bench import spans, trace


def read(run):
    plane = spans.device_plane(run.events)
    if not plane:
        return None
    gaps = trace.idle_gaps(run.events, plane, *spans.bounds(run.events))
    waiting = trace.union(spans.intervals(run.events, spans.IDLE))
    ns = sum(e - s for s, e in gaps) - spans.overlap_ns(gaps, waiting)
    return 100.0 * ns * 1e-9 / run.trace_window_s
