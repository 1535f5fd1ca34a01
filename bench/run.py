#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` (see ``bench/registry.py``).  One process: load and
warm up the cell's own shapes (set-up), measure for ``--seconds``, read
the device's peak memory, free the system's state, then check a sample
of the answers against ``bench/reference/`` and print, as the last line
of standard output, one JSON object::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, "breakdown": {...}, "checks": {...}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window (and
``breakdown``).  ``checks`` holds each number the check compared beside
its limit; the same lines end standard error.

Off a TPU the cell runs at its rehearsal size and the command exits 1
without a result.  A device kind missing from ``bench/peaks.json`` is an
error.  ``--fault`` plants a fault or the lower-precision control (see
``bench/faults.py``); measured runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileCounter:
    """Backend compiles in this process, from JAX's monitoring hook (a
    process-wide listener, installed once)."""

    count = 0
    _installed = False

    @classmethod
    def install(cls) -> None:
        if cls._installed:
            return
        from jax import monitoring

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.count += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        cls._installed = True


def rehearsal(spec: dict) -> dict:
    """A config or traffic file at its rehearsal size."""
    return {k: v for k, v in dict(spec, **spec.get("rehearsal", {})).items()
            if k != "rehearsal"}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(bench, name: str, *, seed: int, seconds: float, trace: bool,
             rehearse: bool, fault=None, t_start: float = None) -> dict:
    """Set up, measure, check and read the metrics of one cell; returns
    the result object (without the device check of :func:`main`)."""
    import jax

    from bench import data, faults, spans, trace_reduce

    t_start = time.perf_counter() if t_start is None else t_start
    cell = bench.cell(name)
    cfg, traffic = bench.config(cell["config"]), bench.traffic(name)
    if rehearse:
        cfg, traffic = rehearsal(cfg), rehearsal(traffic)
    op = bench.op(traffic["op"])
    driver_mod = bench.driver(traffic["driver"])
    if fault is not None:
        faults.install(fault)
    if trace:
        spans.install()
    devices = jax.devices()[:int(cell["chips"])]
    CompileCounter.install()
    drv = driver_mod.Driver(op=op, cfg=cfg, traffic=traffic, seed=seed,
                            root=data.root_key(seed))
    drv.setup(seconds)
    setup_s = time.perf_counter() - t_start
    compiles0 = CompileCounter.count
    session = trace_reduce.Session() if trace else None
    if session:
        session.start()
    drv.window(seconds)
    view = session.stop() if session else None
    window_compiles = CompileCounter.count - compiles0
    peak = memory_peak(devices)
    drv.free()
    numbers = drv.verify()
    checked = numbers.pop("checked")
    checks = {k: {"value": v, "limit": op.LIMITS[k]}
              for k, v in numbers.items()}
    correct = checked > 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())

    run = types.SimpleNamespace(
        cell=cell, config=cfg, traffic=traffic, seconds=seconds,
        setup_s=setup_s, jobs=drv.jobs, trace=view, op=traffic["op"])
    metrics = {}
    for metric in bench.metrics(name, "per_layer" if trace
                                else "end_to_end"):
        value = bench.reader(metric["name"]).read(run)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {metric['name']} "
                                   "found nothing to read")
            continue
        metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}

    log(f"cell {name}: seed {seed}, {len(run.jobs)} jobs in the window, "
        f"set-up {setup_s:.3f} s, compiles in the window {window_compiles}, "
        f"peak device bytes {peak}")
    for job in run.jobs:
        log(f"  job {job['job']}: {job['seconds']:.6f} s, "
            f"{job['units']} units, k_workload {job['k_workload']}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    # a job that fails raises, and the run prints no result
    result = {"correct": bool(correct), "attempted": len(run.jobs),
              "failed": 0, "metrics": metrics, "device": device}
    if view is not None:
        device["busy_s"] = view.busy_s
        device["window_s"] = view.window_s
        result["breakdown"] = view.breakdown()
    result["checks"] = checks
    log(f"checked {checked} answers; correct={correct}")
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return result


def main(argv=None) -> int:
    from bench.faults import FAULTS
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=FAULTS, default=None)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    from bench.registry import Benchmark
    bench = Benchmark(ROOT)
    cell = bench.cell(args.workload)
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as exc:
        log(f"the system under test is not here ({exc}); no result")
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    import jax
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    log(f"devices: {devices}")
    if on_tpu:
        bench.peaks(devices[0].device_kind)
        if len(devices) < int(cell["chips"]):
            log(f"cell {args.workload} needs {cell['chips']} chips, JAX "
                f"finds {len(devices)}; no result")
            return 2
    result = run_cell(bench, args.workload, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      rehearse=not on_tpu, fault=args.fault,
                      t_start=T_START)
    if not on_tpu:
        log(f"rehearsal on {devices[0].platform} passed through every "
            f"phase (correct={result['correct']}); no TPU, so no result")
        return 1
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
