"""Milliseconds a live row waits per token at the engine: over each pair
of consecutive ``engine.window.k<K>`` phases with no ``engine.idle``
between them, the time from the first's end to the second's, summed and
divided by the second windows' summed K. Admission, prefills and the
engine's bookkeeping between windows count."""

import bisect

from bench import spans


def read(run):
    if not spans.device_plane(run.events):
        return None
    wins = spans.windows(run.events)
    waits = [s for s, _ in spans.intervals(run.events, spans.IDLE)]
    ns, steps = 0.0, 0
    for (_, end0, _), (start1, end1, k1) in zip(wins, wins[1:]):
        i = bisect.bisect_left(waits, end0)
        if i < len(waits) and waits[i] < start1:
            continue                    # the engine ran out of work between
        ns += end1 - end0
        steps += k1
    return ns * 1e-6 / steps if steps else None
