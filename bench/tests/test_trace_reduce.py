"""The profiler-trace reduction, on small traces: a hand-made one whose
answers are known, and an excerpt recorded on a TPU v5 lite."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.trace_reduce import View, gaps, parse_op, union

KV = ('%bitonic_sort_kv.1 = (f32[8,1,1,65536]{3,2,1,0:T(1,128)S(1)}, '
      's32[8,1,1,65536]{3,2,1,0:T(1,128)}) custom-call(f32[8,1,1,65536]'
      '{3,2,1,0:T(1,128)S(1)} %reshape.0), custom_call_target='
      '"tpu_custom_call"')
XLA_SORT = ('%sort.3 = (f32[16,4096]{1,0:T(8,128)}, s32[16,4096]{1,0:T(8,'
            '128)}) sort(f32[16,4096]{1,0:T(8,128)} %bitcast.89)')
FUSION = ('%fusion.14 = f32[2048]{0:T(1024)} fusion(f32[16,128]{1,0:T(8,'
          '128)} %bitcast.89), kind=kCustom')
MS = 1_000_000


def test_parse_op():
    kv = parse_op(KV)
    assert kv == {"name": "bitonic_sort_kv",
                  "label": "bitonic_sort_kv.1 = (f32[8,1,1,65536], "
                           "s32[8,1,1,65536])",
                  "opcode": "custom-call", "pallas": True}
    assert parse_op(XLA_SORT)["opcode"] == "sort"
    assert not parse_op(XLA_SORT)["pallas"]
    assert parse_op("not hlo")["name"] == "not"


def test_union_and_gaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert gaps([(1, 2), (4, 6)], [(0, 5), (5.5, 10)]) == [
        (0, 1), (2, 4), (6, 10)]


@pytest.fixture
def view():
    # two windows of 10 ms; ops overlap each other and the window edges
    ops = {"0": [[KV, 1 * MS, 3 * MS], [FUSION, 2 * MS, 4 * MS],
                 [XLA_SORT, 8 * MS, 12 * MS], [FUSION, 15 * MS, 16 * MS],
                 [KV, 30 * MS, 31 * MS]]}      # outside both windows
    windows = [["bench.job", 0, 10 * MS], ["bench.job", 14 * MS, 24 * MS]]
    host = [["statjoin.plan", 4 * MS, 8 * MS],
            ["smms.sort", 0, 24 * MS],
            ["D2H Dispatch", 16 * MS, 18 * MS]]
    return View(ops=ops, windows=windows, host=host)


def test_busy_is_the_union_inside_the_windows(view):
    assert view.window_s == pytest.approx(0.020)
    # [1, 4] + [8, 10] + [14, 14]... : 3 + 2 + 1 (the 15-16 fusion) ms
    assert view.busy_s == pytest.approx(0.006)
    assert view.op_seconds() == pytest.approx(0.002 + 0.002 + 0.002 + 0.001)
    assert view.op_seconds(lambda op: op["opcode"] == "sort") == \
        pytest.approx(0.002)


def test_kernel_attribution(view):
    calls = view.op_events(lambda op: op["pallas"])
    assert [(op["name"], s) for op, s in calls] == [
        ("bitonic_sort_kv", pytest.approx(0.002))]
    assert view.op_seconds(lambda op: op["pallas"]) == pytest.approx(0.002)


def test_idle_gaps_are_named_by_the_innermost_host_span(view):
    named = dict(view.idle_gaps())
    # gaps: [0,1] [4,8] (statjoin.plan), [14,15] [16,24] (D2H for 2 ms)
    assert named["statjoin.plan"] == pytest.approx(0.004)
    assert named["smms.sort"] == pytest.approx(0.001 + 0.001 + 0.008)
    assert sum(named.values()) == pytest.approx(view.window_s - view.busy_s)
    top = view.breakdown()["device_ops"]
    assert top[0][0].startswith(("bitonic_sort_kv.1", "fusion.14",
                                 "sort.3"))


RECORDED = Path(__file__).with_name("data") / "trace_query_v5e_excerpt.json"


def test_recorded_v5e_excerpt():
    raw = json.loads(RECORDED.read_text())
    v = View(ops=raw["ops"], windows=raw["windows"], host=raw["host"])
    # busy: the union, checked against a brute-force 100 ns timeline
    (w0, w1), = [(a, b) for _, a, b in raw["windows"]]
    grid = np.zeros(int((w1 - w0) // 100) + 1, bool)
    for _, a, b in raw["ops"]["0"]:
        grid[int((a - w0) // 100):int((b - w0) // 100)] = True
    assert v.busy_s == pytest.approx(grid.sum() * 100e-9, rel=0.02)
    assert 0 < v.busy_s <= v.window_s
    # kernels: Pallas custom calls count, XLA's AllocateBuffer calls not
    kernels = {op["name"] for op, _ in v.op_events(lambda op: op["pallas"])}
    assert {"bitonic_sort", "searchsorted"} <= kernels
    assert not any(n.startswith("custom-call") for n in kernels)
    assert sum(s for _, s in v.idle_gaps(k=1000)) == pytest.approx(
        v.window_s - v.busy_s)
