"""Mean container size per request on the wire, in kbit: the bytes the
benchmark's wire meter was handed in the window, over the requests."""


def read(run):
    return 8 * run.wire_bytes / run.drained.n / 1e3
