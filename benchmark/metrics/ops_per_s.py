"""ops_per_s: requests completed in the window over its seconds."""

from stats import rate


def read(run):
    return rate(run.completed, run.log.window_s)
