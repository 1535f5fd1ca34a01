"""Set-up: process start to the window's start (loading, data, warm-up,
compiles or cache loads), on the host clock."""


def read(run):
    return run.setup_s
