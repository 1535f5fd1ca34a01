"""Device busy time per join call: the union of device op intervals
inside the window's call spans, over the calls, in ms."""


def read(run):
    view = run.trace
    if view is None or not view.ops or not run.jobs:
        return None
    return 1e3 * view.busy_s / len(run.jobs)
