"""Sort operations: ``cluster.sort`` over (float32 key, int32 record id)
records, checked against ``bench/reference/sort.py``."""
from __future__ import annotations

import numpy as np

from bench import data
from bench.reference.bounds import ROUNDS, THEOREM, workload_bound
from bench.reference.sort import compare_sort

# Each number the check compares, and its limit: wrong positions are
# counted exactly, and the theorems bound rounds and workload.
LIMITS = {"keys_wrong": 0, "payload_wrong": 0, "rounds_over": 0,
          "workload_over_bound": 1.0}


def job_args(cfg: dict, job: dict) -> dict:
    """Keyword sets for one job of this config: ``make`` for
    :func:`make_input`, ``call`` for :func:`call`, ``bounds`` for
    :func:`guarantees`.  ``job`` picks ``keys`` and ``payload``, and may
    name another ``algorithm`` than the config's."""
    t, n = int(cfg["t"]), int(cfg["records_per_chip"])
    return {"make": dict(cfg=cfg, t=t, n=n, keys=job["keys"],
                         payload=bool(job.get("payload", True))),
            "call": dict(algorithm=job.get("algorithm", cfg["algorithm"])),
            "bounds": dict(t=t, n=n, r=int(cfg["r"]))}


def key_dist(cfg: dict, name: str) -> tuple:
    """The config's key distribution ``name`` as a hashable tuple."""
    d = cfg["key_distributions"][name]
    if d["law"] == "uniform":
        return ("uniform", float(d["low"]), float(d["high"]))
    return ("zipf", float(d["exponent"]), int(d["ranks"]))


def make_input(key, *, cfg: dict, t: int, n: int, keys: str,
               payload: bool = True):
    """(x, values or None) on the device, from ``key``."""
    x, v = data.sort_input(key, t=t, n=n, dist=key_dist(cfg, keys))
    return (x, v if payload else None)


def call(inputs, *, algorithm: str):
    """One job through the front door; the answer is on the host."""
    from repro import cluster
    x, v = inputs
    (keys, vals), report = cluster.sort(x, values=v, algorithm=algorithm)
    return (keys, vals), report


def units(inputs, answer, report) -> int:
    """Records sorted."""
    return int(np.prod(inputs[0].shape))


def keep(answer):
    """The answer is already on the host, in arrays of its own."""
    return answer


def host_inputs(inputs):
    x, v = inputs
    return np.asarray(x), (None if v is None else np.asarray(v))


def check(inputs_host, kept) -> dict:
    """Counts of wrong answers against the reference."""
    x, _ = inputs_host
    keys, vals = kept
    return compare_sort(x, keys, vals)


def guarantees(report, *, t: int, n: int, r: int = 2) -> dict:
    """Rounds, and the largest machine's workload over its theorem's
    bound, from the algorithm's (alpha, k) report (None: no report)."""
    if report is None:
        return {"rounds_over": None, "workload_over_bound": None}
    algo = _algorithm(report.algorithm)
    bound = workload_bound(THEOREM[algo], n=n, t=t, r=r)
    return {"rounds_over": int(report.alpha) - ROUNDS[algo],
            "workload_over_bound": float(np.max(report.workload)) / bound}


def _algorithm(name: str) -> str:
    low = name.lower()
    for algo in ROUNDS:
        if low.startswith(algo):
            return algo
    raise ValueError(f"no stated guarantee for algorithm {name!r}")


def regenerate(key, **kw):
    """The inputs of one job again, from its key, on the host."""
    return host_inputs(make_input(key, **kw))
