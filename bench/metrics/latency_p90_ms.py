"""90th percentile of the latencies that ``latency_p50_ms`` reads."""

from bench.metrics import latencies_ms


def read(run):
    lat = latencies_ms(run)
    return float(__import__("numpy").percentile(lat, 90)) if lat else None
