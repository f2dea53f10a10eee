"""Process start to the window's start (host clock): imports, weights,
images, the system's construction and the warm-up serve calls, compiles or
compile-cache loads included."""


def read(run):
    return run.setup_s
