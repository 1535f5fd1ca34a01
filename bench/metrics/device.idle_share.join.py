"""Share of the measured window in which no op ran on the device:
1 - (union of device op intervals) / window, from the profiler trace,
within the benchmark's own window spans, averaged over the chips."""
from bench.metrics._rates import idle_share


def read(run):
    return idle_share(run.trace)
