"""goodput_GBps: attachment bytes delivered in both directions by the
requests completed in the window, over its seconds; 1 GB = 1e9 B."""

from stats import rate


def read(run):
    per = run.bench.bytes_per_request
    if not per:
        return None
    return rate(run.completed * per, run.log.window_s) / 1e9
