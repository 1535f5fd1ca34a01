"""Share of device busy time spent in cross-chip collectives (all-to-all,
all-gather, all-reduce, reduce-scatter, collective-permute, with their
async start and done forms), from the profiler trace, averaged over the
chips, in percent; None where the trace holds no collective (one chip)."""
from bench.metrics._rates import share

COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")


def is_collective(op: dict) -> bool:
    return any(c in op["opcode"] or c.replace("-", "_") in op["name"]
               for c in COLLECTIVES)


def read(run):
    view = run.trace
    if view is None or view.busy_s <= 0:
        return None
    seconds = view.op_seconds(is_collective)
    return share(seconds, view.busy_s) if seconds > 0 else None
