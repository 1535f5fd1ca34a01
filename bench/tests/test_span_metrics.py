"""The readers of the program's own spans, on hand-built views."""
import types

import pytest

from bench.registry import Benchmark
from bench.trace_reduce import View

MS = 1_000_000


def run_of(host, jobs=2, windows=((0, 10), (20, 30))):
    view = View(ops={"0": []},
                windows=[["bench.job", a * MS, b * MS] for a, b in windows],
                host=[[n, a * MS, b * MS] for n, a, b in host])
    return types.SimpleNamespace(trace=view, jobs=[{}] * jobs)


def read(metric, run):
    return Benchmark().reader(metric).read(run)


def test_same_name_spans_nested_count_once():
    # the benchmark's wrapper around the program's span of the same name
    run = run_of([("statjoin.plan", 1, 5), ("statjoin.plan", 2, 4)], jobs=1)
    assert read("statjoin.plan_ms.join", run) == pytest.approx(4.0)


def test_span_is_clipped_to_the_windows():
    run = run_of([("smms.fetch", 8, 22)], jobs=1)
    assert read("smms.fetch_ms.sort", run) == pytest.approx(2.0 + 2.0)


def test_time_is_divided_by_the_jobs():
    run = run_of([("statjoin.route", 1, 4), ("statjoin.route", 21, 24),
                  ("statjoin.statistics", 4, 5)], jobs=2)
    assert read("statjoin.route_ms.join", run) == pytest.approx(3.0)
    assert read("statjoin.statistics_ms.join", run) == pytest.approx(0.5)


def test_absent_span_reads_none_and_absent_compile_reads_zero():
    run = run_of([("substrate.run", 1, 9)])
    for metric in ("statjoin.statistics_ms.join", "statjoin.plan_ms.join",
                   "statjoin.route_ms.join", "smms.fetch_ms.sort",
                   "smms.compact_ms.sort", "smms.capacity_attempts.sort"):
        assert read(metric, run) is None, metric
    assert read("substrate.compiles.sort", run) == 0
    assert read("substrate.compiles.join", run) == 0
    untraced = types.SimpleNamespace(trace=None, jobs=[{}])
    assert read("substrate.compiles.sort", untraced) is None


def test_counts_start_inside_a_window():
    run = run_of([("capacity.attempt", 1, 3), ("capacity.attempt", 3, 9),
                  ("capacity.attempt", 21, 29), ("capacity.attempt", 12, 21),
                  ("substrate.compile", 2, 3), ("substrate.compile", 15, 25)])
    assert read("smms.capacity_attempts.sort", run) == pytest.approx(1.5)
    assert read("substrate.compiles.join", run) == 1
