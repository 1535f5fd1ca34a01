"""Shared arithmetic of the readers of the program's own spans.

The program writes its ``repro.obs`` spans into the profiler trace as
``TraceAnnotation``s; :class:`bench.trace_reduce.View` keeps them in
``host`` as ``[name, start_ns, end_ns]``.  A time is the union of a
name's intervals inside the window, so a span and the benchmark's own
wrapper of the same name (``bench/spans.json``) count once.
"""
from __future__ import annotations

from bench.trace_reduce import clip, length, union


def _intervals(view, name: str) -> list:
    return [(a, b) for n, a, b in view.host if n == name]


def span_ms(run, name: str) -> float | None:
    """In-window time of the spans called ``name``, per job, in ms;
    None where the trace holds no such span."""
    view = run.trace
    if view is None or not run.jobs:
        return None
    intervals = _intervals(view, name)
    if not intervals:
        return None
    inside = union(clip(intervals, view.windows))
    return length(inside) * 1e-6 / len(run.jobs)


def span_count(run, name: str) -> int | None:
    """How many spans called ``name`` start inside a window; 0 where the
    trace holds none."""
    view = run.trace
    if view is None:
        return None
    return sum(1 for a, _ in _intervals(view, name)
               if any(w0 <= a < w1 for w0, w1 in view.windows))


def span_count_per_job(run, name: str) -> float | None:
    """:func:`span_count` over the window's jobs, for a span every job
    opens at least once: None where the trace holds none (a program
    that does not write it)."""
    count = span_count(run, name)
    if not count or not run.jobs:
        return None
    return count / len(run.jobs)
