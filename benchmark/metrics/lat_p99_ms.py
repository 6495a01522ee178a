"""lat_p99_ms: the 99th percentile of the latency of every request
issued in the window that succeeded, issue to completion (host clock);
one still in flight at the close is waited for and counts."""

from stats import percentile


def read(run):
    lat = run.log.latencies_ms()
    return percentile(lat, 99) if lat else None
