"""Find the benchmark's parts by name.

Everything that belongs to one configuration, one cell or one metric is
a file of its own; this module maps the names in ``BENCHMARK.json`` to
those files, so a new cell, configuration or metric is new files and
entries only:

* ``BENCHMARK.json``            the cells, configurations and metrics;
* ``bench/configs/<config>.json``   a deployment (the config's ``file``);
* ``bench/workloads/<cell>.json``   the cell's traffic mix: the driver
  kind and its parameters;
* ``bench/traffic/<kind>.py``       the drivers (``closed_jobs``);
* ``bench/kinds/<op>.py``           how one operation is generated,
  called and checked (``sort``, ``join``);
* ``bench/metrics/<metric>.py``     one reader per metric.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path (metric files carry dots in their names)."""
    name = "bench_part_" + path.stem.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """``BENCHMARK.json`` and the files it names, under one root."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench_dir = self.root / "bench"
        self.spec = load_json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        for cell in self.spec["workloads"]:
            if cell["name"] == name:
                return cell
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[c['name'] for c in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        for cfg in self.spec["configs"]:
            if cfg["name"] == name:
                return load_json(self.root / cfg["file"])
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, cell_name: str) -> dict:
        return load_json(self.bench_dir / "workloads" / f"{cell_name}.json")

    def metrics(self, cell_name: str, section: str) -> list:
        """The metrics of ``section`` ("end_to_end" or "per_layer") that
        this cell reports: those listing it, and those listing none."""
        cell = self.cell(cell_name)
        out = []
        for metric in self.spec[section]:
            cells = metric.get("workloads")
            if cells is not None and cell_name not in cells:
                continue
            if cells is None and section == "per_layer":
                moved = self.metric("end_to_end", metric["moves"])
                if moved.get("workloads") not in (None, []) and \
                        cell["name"] not in moved["workloads"]:
                    continue
            out.append(metric)
        return out

    def metric(self, section: str, name: str) -> dict:
        for metric in self.spec[section]:
            if metric["name"] == name:
                return metric
        raise KeyError(f"no {section} metric {name!r}")

    def driver(self, kind: str):
        return load_module(self.bench_dir / "traffic" / f"{kind}.py")

    def op(self, op: str):
        return load_module(self.bench_dir / "kinds" / f"{op}.py")

    def reader(self, metric_name: str):
        return load_module(self.bench_dir / "metrics" / f"{metric_name}.py")

    def peaks(self, device_kind: str) -> dict:
        table = load_json(self.bench_dir / "peaks.json")
        try:
            return table["devices"][device_kind]
        except KeyError:
            raise KeyError(
                f"device kind {device_kind!r} is not in bench/peaks.json "
                f"(known: {sorted(table['devices'])}); add its published "
                "peaks before measuring on it") from None
