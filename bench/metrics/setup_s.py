"""Set-up: process start to the window's open (weights, warm-up, compiles)."""


def read(run):
    return run.setup_s
