"""Device milliseconds per decode step: the time some operation ran on the
device inside ``engine.window.k<K>`` phases (the windows less the device's
idle gaps in them), over the phases' summed K."""

from bench import spans, trace


def read(run):
    plane = spans.device_plane(run.events)
    wins = plane and spans.windows(run.events)
    if not wins:
        return None
    gaps = trace.idle_gaps(run.events, plane, *spans.bounds(run.events))
    ivals = [(s, e) for s, e, _ in wins]
    ns = sum(e - s for s, e in ivals) - spans.overlap_ns(gaps, ivals)
    return ns * 1e-6 / sum(k for _, _, k in wins)
