"""Share of device busy time spent in XLA's own sort op: the fallback
that kernels/ops.py takes for rows past the Pallas kernels' lanes."""
from bench.metrics._rates import share


def read(run):
    view = run.trace
    if view is None or view.busy_s <= 0:
        return None
    seconds = view.op_seconds(lambda op: op["opcode"] == "sort")
    return share(seconds, view.busy_s) if seconds > 0 else None
