"""Requests completed over the time from the window's start to the last
completion (host clock)."""
from benchlib.stats import rate


def read(run):
    d = run.drained
    return rate(d.n, d.t0, d.last_end)
