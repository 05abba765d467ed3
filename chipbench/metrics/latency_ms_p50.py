"""The 50th percentile of the per-request latency, call to
``block_until_ready`` returning, over the untraced part of the window
(one client, closed loop)."""
import statistics

UNIT = "ms"


def read(ctx):
    lat = ctx["host"].get("latency_s")
    if not lat or len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=100, method="inclusive")[49] * 1e3
