"""Capacity attempts per sort job: the program's ``capacity.attempt``
spans that start inside the window, over the jobs (1.0 when no job
overflowed its Theorem 1 buffers)."""
from bench.metrics._spans import span_count_per_job


def read(run):
    return span_count_per_job(run, "capacity.attempt")
