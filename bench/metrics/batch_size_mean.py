"""Requests per micro-batch in the window, from the gateway's per-request
telemetry (``RequestRecord.batch_size``)."""


def read(run):
    if not run.batches:
        return None
    return sum(n for n, _ in run.batches) / len(run.batches)
