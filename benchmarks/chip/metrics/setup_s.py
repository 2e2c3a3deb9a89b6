"""Set-up: process start to the window's start (weights, executables from
the compile cache, warm-up, cache priming)."""


def read(run):
    return run["setup_s"]
