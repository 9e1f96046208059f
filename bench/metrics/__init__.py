"""One reader per metric: ``metrics/<name>.py`` defines ``read(run)``,
which returns the metric's value or ``None`` where the run holds nothing to
read. A name ``<base>.<cells>`` (a quantity split by the end-to-end metric
it moves) falls back to ``metrics/<base>.py``. ``run`` is the
``bench.run.Run`` of one finished run."""

from __future__ import annotations


def latencies_ms(run) -> list[float]:
    """Due-to-reply milliseconds of the window's successful requests."""
    return [(r.done - r.due_s) * 1e3 for r in run.requests
            if r.ok and r.due_s is not None]
