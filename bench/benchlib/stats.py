"""Percentile and rate arithmetic of the end-to-end metrics."""
from __future__ import annotations

import numpy as np


def percentile(values, p: float) -> float:
    """numpy's linear percentile over every value given: a tail is the
    tail of all requests."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(values, p))


def rate(completed: int, t_start: float, t_last: float) -> float:
    """Completions per second from the window's start to the last
    completion: a batch cut at the window's edge does not step the rate."""
    if completed <= 0 or t_last <= t_start:
        raise ValueError("no completions to rate")
    return completed / (t_last - t_start)
