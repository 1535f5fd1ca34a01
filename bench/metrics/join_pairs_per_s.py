"""Result pairs of the window's whole join calls over their time (host
clock; each call ends in block_until_ready on its answer)."""
from bench.metrics._rates import rate


def read(run):
    return rate(run.jobs) if run.op == "join" else None
