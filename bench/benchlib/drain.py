"""The window loop: hands every request that is due to one serve call.

The gateway has no real-time loop of its own, so this loop stands in for
one. While the window runs it takes every request that is due and not yet
served (at most ``take``) and serves them in one call; a request's latency
runs from its due time to the return of that call. Requests due inside the
window are all served, even when the drain runs past its end, so a stall
shows in the tail. A backlog mix (every request due at the window's start)
takes ``take`` per call and starts no call after the window's end.

Clock, sleep and the span factory are arguments, so tests drive the loop
on a fake clock.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Call:
    first: int          # requests first .. first + n - 1
    n: int
    t_start: float
    t_end: float


@dataclass(frozen=True)
class Drained:
    t0: float               # window start
    due: np.ndarray         # absolute due time per request taken
    calls: list             # [Call], in order
    wake_late_s: float      # worst lateness of a wake-up after an idle wait

    @property
    def n(self) -> int:
        return len(self.due)

    def per_request(self, field: str) -> np.ndarray:
        out = np.empty(self.n)
        for c in self.calls:
            out[c.first:c.first + c.n] = getattr(c, field)
        return out

    @property
    def latency_s(self) -> np.ndarray:
        return self.per_request("t_end") - self.due

    @property
    def queue_wait_s(self) -> np.ndarray:
        return self.per_request("t_start") - self.due

    @property
    def last_end(self) -> float:
        return self.calls[-1].t_end


def _null_span(name: str):
    return contextlib.nullcontext()


def drain(serve, *, clock, sleep, window_s: float,
          offsets: np.ndarray | None, take: int | None,
          span=_null_span) -> Drained:
    """Run the window. ``serve(first, n)`` serves requests first..first+n-1.

    ``offsets`` are due times from the window's start (sorted); None means
    a backlog, where every request is due at the start.
    """
    calls: list[Call] = []
    wake_late = 0.0
    t0 = clock()
    end = t0 + window_s
    if offsets is None:
        first = 0
        while clock() < end:
            with span("bench.serve"):
                ts = clock()
                serve(first, take)
                calls.append(Call(first, take, ts, clock()))
            first += take
        return Drained(t0, np.full(first, t0), calls, 0.0)

    due = t0 + np.asarray(offsets, np.float64)
    due = due[due < end]
    first, n = 0, len(due)
    waited = False
    while first < n:
        now = clock()
        if due[first] > now:
            with span("bench.wait"):
                sleep(due[first] - now)
            waited = True
            continue
        stop = int(np.searchsorted(due, now, side="right"))
        if take:
            stop = min(stop, first + take)
        with span("bench.serve"):
            ts = clock()
            if waited:
                wake_late = max(wake_late, ts - due[first])
                waited = False
            serve(first, stop - first)
            calls.append(Call(first, stop - first, ts, clock()))
        first = stop
    return Drained(t0, due, calls, wake_late)
