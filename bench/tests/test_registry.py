"""The harness is driven by data: a new cell, configuration and metric
are new files and entries, with no edit to a file that exists."""
import json
import shutil
import subprocess
import sys

import pytest

from bench.registry import ROOT, Benchmark
from bench.run import run_cell

NEW_CONFIG = {
    "name": "tiny-sort", "source": "a test fixture", "kind": "sort",
    "records_per_chip": 2048, "t": 4, "algorithm": "smms", "r": 2,
    "key_distributions": {"uniform": {"law": "uniform", "low": 1.0,
                                      "high": 1000.0}},
}
NEW_TRAFFIC = {"driver": "closed_jobs", "op": "sort",
               "job": {"keys": "uniform", "payload": True}}
NEW_READER = '''"""Jobs run in the window."""


def read(run):
    return float(len(run.jobs)) if run.jobs else None
'''


@pytest.fixture
def grown(tmp_path):
    """A copy of the benchmark with one more cell, config and metric,
    added as files and entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    (tmp_path / "bench/configs/tiny-sort.json").write_text(
        json.dumps(NEW_CONFIG))
    (tmp_path / "bench/workloads/tiny-sort.smms-uniform.json").write_text(
        json.dumps(NEW_TRAFFIC))
    (tmp_path / "bench/metrics/jobs_in_window.tiny.py").write_text(NEW_READER)
    spec["configs"].append({"name": "tiny-sort", "source": "test",
                            "file": "bench/configs/tiny-sort.json",
                            "reduced": [], "why": "fixture"})
    spec["workloads"].append({"name": "tiny-sort.smms-uniform",
                              "config": "tiny-sort",
                              "traffic": "smms-uniform", "chips": 1,
                              "why": "fixture"})
    for metric in spec["end_to_end"]:
        if metric["name"] == "sort_records_per_s":
            metric["workloads"].append("tiny-sort.smms-uniform")
    spec["per_layer"].append({"name": "jobs_in_window.tiny", "unit": "jobs",
                              "better": "higher", "source": "host_clock",
                              "layer": "harness",
                              "moves": "sort_records_per_s",
                              "workloads": ["tiny-sort.smms-uniform"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    assert all(after[p] == b for p, b in before.items())   # nothing edited
    return tmp_path


def test_new_cell_config_and_metric_need_only_files(grown):
    bench = Benchmark(grown)
    e2e = run_cell(bench, "tiny-sort.smms-uniform", seed=1, seconds=0.3,
                   trace=False, rehearse=True)
    assert e2e["correct"], e2e["checks"]
    assert set(e2e["metrics"]) == {"setup_s", "sort_records_per_s"}
    layered = run_cell(bench, "tiny-sort.smms-uniform", seed=2,
                       seconds=0.3, trace=True, rehearse=True)
    assert layered["metrics"]["jobs_in_window.tiny"]["value"] >= 1
    assert layered["metrics"]["jobs_in_window.tiny"]["unit"] == "jobs"


def test_every_named_file_exists():
    bench = Benchmark()
    for cfg in bench.spec["configs"]:
        assert (ROOT / cfg["file"]).is_file()
    for cell in bench.spec["workloads"]:
        traffic = bench.traffic(cell["name"])
        assert (ROOT / "bench/traffic" / f"{traffic['driver']}.py").is_file()
        assert (ROOT / "bench/kinds" / f"{traffic['op']}.py").is_file()
    for section in ("end_to_end", "per_layer"):
        for metric in bench.spec[section]:
            assert (ROOT / "bench/metrics" / f"{metric['name']}.py").is_file()


def test_unknown_device_kind_is_an_error():
    bench = Benchmark()
    assert bench.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v99"):
        bench.peaks("TPU v99")


def test_off_a_tpu_the_command_claims_no_result():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "paper-sort.smms-zipf", "--seed", str((1 << 32) + 3),
         "--seconds", "0.2", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=600,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT)})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU, so no result" in proc.stderr


def test_without_the_program_the_command_fails(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "paper-sort.smms-zipf", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=tmp_path,
        timeout=300, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                          "HOME": str(tmp_path)})
    assert proc.returncode != 0 and proc.stdout.strip() == ""
