"""95th percentile of request latency, due time to answer, over every
request due in the window (host clock)."""
from benchlib.stats import percentile


def read(run):
    return percentile(run.drained.latency_s, 95) * 1e3
