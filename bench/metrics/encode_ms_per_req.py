"""Host time of the program's entropy-encode stage per request: the
``stage_seconds{stage=pipeline.encode}`` timer (tile, histogram kernel,
rANS encode, container) summed over the window, over its calls."""


def read(run):
    if not run.stage:
        return None
    seconds, calls = run.stage["pipeline.encode"]
    return seconds / calls * 1e3 if calls else None
