"""The four-chip sort cell: its ICI byte count, its trace readers on a
hand-built four-chip view, and its rehearsal on four forced CPU devices."""
import json
import os
import subprocess
import sys
import types

import pytest

from bench.metrics import _ici
from bench.registry import ROOT, Benchmark
from bench.trace_reduce import View

MS = 1_000_000
CELL = "paper-sort-mesh4.smms-uniform-flat"
A2A = ("%all_to_all.22 = f32[1,16,1,64,137627]{4,1,2,0,3} all-to-all("
       "%copy.33), channel_id=1, replica_groups={{0,1,2,3}}")
GATHER_START = ("%all-gather-start.1 = (f32[1,129], f32[64,129]) "
                "all-gather-start(%p), channel_id=2")
FUSION = "%fusion.13 = f32[140929024]{0} fusion(%p.1), kind=kLoop"


def test_round3_bytes_by_hand():
    # t = 8 over 4 chips: v = 2 machines a chip, each sends 6 of its 8
    # tiles off the chip; m = 1000, int(2.1 * 1000) = 2100 records a
    # machine, tiles of ceil(2100 / 8) = 263 records of 8 bytes
    assert _ici.cap_pair(8000, 8, 2.1) == 263
    assert _ici.round3_bytes_per_chip(8, 4, 8000, 2.1) == 2 * 6 * 263 * 8
    # one chip: nothing crosses the interconnect
    assert _ici.round3_bytes_per_chip(8, 1, 8000, 2.1) == 0
    assert _ici.theorem1_factor(1 << 28, 64, 2) == pytest.approx(
        (2.0 + 64 * 64 / 2 ** 28) * 1.05)


def test_round3_tile_is_the_programs():
    # the tile the compiled v5e program ships at the cell's size
    # (f32[1,16,1,64,137627] above: 16 machines, 64 tiles each)
    n, t = 1 << 28, 64
    assert _ici.cap_pair(n, t, _ici.theorem1_factor(n, t, 2)) == 137627


def view_of(per_chip, windows=((0, 10), (20, 30)), host=()):
    """Four device planes, each holding ``per_chip(d)``'s ops as
    ``(hlo, start_ms, end_ms)``."""
    return View(
        ops={str(d): [[h, a * MS, b * MS] for h, a, b in per_chip(d)]
             for d in range(4)},
        windows=[["bench.job", a * MS, b * MS] for a, b in windows],
        host=[[n, a * MS, b * MS] for n, a, b in host])


def run_of(view, jobs=2):
    bench = Benchmark()
    cell = bench.cell(CELL)
    return types.SimpleNamespace(trace=view, jobs=[{}] * jobs, cell=cell,
                                 config=bench.config(cell["config"]))


def read(metric, run):
    return Benchmark().reader(metric).read(run)


def test_collective_share_averages_the_chips():
    # chip d: a fusion 0-4 ms, an all_to_all 4-(5 + d) ms, a gather
    # start 21-22 ms; busy 5 + d + 1 ms, collectives 1 + d + 1 ms
    view = view_of(lambda d: [(FUSION, 0, 4), (A2A, 4, 5 + d),
                              (GATHER_START, 21, 22)])
    busy = sum(6 + d for d in range(4)) / 4
    coll = sum(2 + d for d in range(4)) / 4
    got = read("exchange.collective_share.mesh4", run_of(view))
    assert got == pytest.approx(100 * coll / busy)


def test_collective_share_none_without_collectives():
    view = view_of(lambda d: [(FUSION, 0, 4)] if d == 0 else [])
    assert read("exchange.collective_share.mesh4", run_of(view)) is None


def test_ici_roofline_over_the_all_to_all_time(monkeypatch):
    monkeypatch.setattr(_ici, "peak_bytes_per_s", lambda: 200e9)
    attempts = [("capacity.attempt", 1, 9), ("capacity.attempt", 21, 29)]
    view = view_of(lambda d: [(FUSION, 0, 1), (A2A, 1, 10), (A2A, 21, 30)],
                   host=attempts)
    run = run_of(view, jobs=2)
    cfg = run.config
    n, t = cfg["records_per_chip"], cfg["t"]
    moved = 2 * _ici.round3_bytes_per_chip(
        t, 4, n, _ici.theorem1_factor(n, t, cfg["r"]))
    got = read("exchange.ici_roofline.mesh4", run)
    assert got == pytest.approx(100 * (moved / 200e9) / 18e-3)
    assert 0 < got <= 100


def test_ici_roofline_none_after_a_capacity_retry(monkeypatch):
    monkeypatch.setattr(_ici, "peak_bytes_per_s", lambda: 200e9)
    attempts = [("capacity.attempt", 1, 3), ("capacity.attempt", 3, 9),
                ("capacity.attempt", 21, 29)]
    view = view_of(lambda d: [(A2A, 4, 8)], host=attempts)
    assert read("exchange.ici_roofline.mesh4", run_of(view)) is None
    # no all_to_all on the trace (one chip): nothing to read
    view = view_of(lambda d: [(FUSION, 0, 4)], host=attempts[1:])
    assert read("exchange.ici_roofline.mesh4", run_of(view)) is None


def test_place_ms_reads_the_placement_span():
    view = view_of(lambda d: [], host=[("substrate.place", 1, 4),
                                       ("substrate.place", 21, 23)])
    assert read("substrate.place_ms.mesh4", run_of(view)) == \
        pytest.approx(2.5)
    assert read("substrate.place_ms.mesh4", run_of(view_of(
        lambda d: []))) is None


REHEARSAL = r"""
import os, json, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
from bench.registry import Benchmark
from bench.run import run_cell
bench = Benchmark()
out = {}
for trace in (False, True):
    out[str(trace)] = run_cell(bench, "%s", seed=(1 << 33) + 5,
                               seconds=0.3, trace=trace, rehearse=True)
print("RESULT " + json.dumps(out))
""" % CELL


def test_cell_rehearses_on_four_devices():
    env = {"JAX_PLATFORMS": "cpu", "PATH": os.environ.get("PATH", ""),
           "HOME": os.environ.get("HOME", str(ROOT))}
    proc = subprocess.run([sys.executable, "-c", REHEARSAL, str(ROOT)],
                          capture_output=True, text=True, cwd=ROOT,
                          timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    got = json.loads(line[len("RESULT "):])
    for result in got.values():
        assert result["correct"], result["checks"]
        assert result["device"]["count"] == 4
    assert set(got["False"]["metrics"]) == {"setup_s", "sort_records_per_s"}
    assert got["True"]["metrics"]["substrate.place_ms.mesh4"]["value"] > 0
