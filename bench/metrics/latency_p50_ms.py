"""Median latency of the requests due in the window, each timed from when
it was due until its whole reply arrived."""

from bench.metrics import latencies_ms


def read(run):
    lat = latencies_ms(run)
    return float(__import__("numpy").percentile(lat, 50)) if lat else None
