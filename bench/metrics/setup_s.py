"""Process start to the window's start: imports, weights, prepare, warm-up
(compiles or compile-cache reads) and, offline, the first fill of the slots."""


def read(run):
    return run.setup_s
