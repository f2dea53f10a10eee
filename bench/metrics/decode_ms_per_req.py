"""Host time of the program's batched decode stage per request: the
``stage_seconds{stage=pipeline.decode_batch}`` timer summed over the
window, over the requests served."""


def read(run):
    if not run.stage:
        return None
    seconds, calls = run.stage["pipeline.decode_batch"]
    return seconds / run.drained.n * 1e3 if calls else None
