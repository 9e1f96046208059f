"""Engine-side time to first token, p90: the ``engine.ttft_us.direct`` and
``engine.ttft_us.chunked`` histograms merged (reset at the window's start),
from the engine's submit to the first token's existence."""


def read(run):
    h = run.ttft_us
    return h.percentile(90) / 1e3 if h is not None and h.count else None
