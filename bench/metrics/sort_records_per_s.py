"""Records of the window's whole sort jobs over their time (host clock;
each job ends with its sorted answer on the host)."""
from bench.metrics._rates import rate


def read(run):
    return rate(run.jobs) if run.op == "sort" else None
