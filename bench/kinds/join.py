"""Join operations: ``cluster.join`` of a Zipf-skewed foreign-key side S
into a unique-key side T, checked against ``bench/reference/join.py``."""
from __future__ import annotations

import jax
import numpy as np

from bench import data
from bench.reference.bounds import ROUNDS, THEOREM, workload_bound
from bench.reference.join import compare_join, join_reference

# Each number the check compares, and its limit (see kinds/sort.py).
LIMITS = {"pairs_missing": 0, "pairs_wrong": 0, "pairs_dropped": 0,
          "rounds_over": 0, "workload_over_bound": 1.0}


def job_args(cfg: dict, job: dict) -> dict:
    """As in ``kinds/sort.py``: ``make``, ``call`` and ``bounds``."""
    t = int(cfg["t"])
    return {"make": dict(cfg=cfg, n_s=int(cfg["fk_rows"]),
                         n_t=int(cfg["pk_rows"])),
            "call": dict(algorithm=job.get("algorithm", cfg["algorithm"]),
                         t=t),
            "bounds": dict(t=t)}


def make_input(key, *, cfg: dict, n_s: int, n_t: int):
    """The config's statistics (drawn from its ``statistics_seed``), in
    the row order that ``key`` draws."""
    return data.fk_join_input(key, data.root_key(cfg["statistics_seed"]),
                              n_s=n_s, n_t=n_t,
                              exponent=float(cfg["fk_zipf_exponent"]))


def call(inputs, *, algorithm: str, t: int):
    """One join through the front door, finished on the device."""
    from repro import cluster
    out, report = cluster.join(*inputs, algorithm=algorithm, t_machines=t)
    jax.block_until_ready(out)
    return out, report


def units(inputs, answer, report) -> int:
    """Result pairs the call produced."""
    return int(np.count_nonzero(np.asarray(answer.valid)))


def keep(answer):
    valid = np.asarray(answer.valid).reshape(-1).astype(bool)
    dropped = int(np.max(np.asarray(answer.dropped)))
    return (np.asarray(answer.s_rows).reshape(-1)[valid],
            np.asarray(answer.t_rows).reshape(-1)[valid], dropped)


def host_inputs(inputs):
    return tuple(np.asarray(a) for a in inputs)


def check(inputs_host, kept) -> dict:
    s_keys, _, t_keys, _ = inputs_host
    s_rows, t_rows, dropped = kept
    out = compare_join(join_reference(s_keys, t_keys), s_rows, t_rows)
    out["pairs_dropped"] = dropped
    return out


def guarantees(report, *, t: int) -> dict:
    """Rounds, and the largest machine's result pairs over Theorem 6's
    2W/t (None: no report)."""
    if report is None:
        return {"rounds_over": None, "workload_over_bound": None}
    bound = workload_bound(THEOREM["statjoin"], n=0, t=t,
                           w=int(report.n_out))
    return {"rounds_over": int(report.alpha) - ROUNDS["statjoin"],
            "workload_over_bound": float(np.max(report.workload)) / bound}


def regenerate(key, **kw):
    return host_inputs(make_input(key, **kw))
