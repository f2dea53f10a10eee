"""95th percentile of the wait from a request's due time to the start of
the serve call that answers it (the drain loop's queue; host clock)."""
from benchlib.stats import percentile


def read(run):
    return percentile(run.drained.queue_wait_s, 95) * 1e3
