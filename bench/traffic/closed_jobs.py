"""Closed loop of whole jobs: the next job starts when the last returned.

One caller submits a job of the cell's operation, waits for its whole
answer, and submits the next: the batch path.  Each job gets fresh
inputs, ``fold_in(seed, i)``, made on the device before its clock
starts, so no cache can serve it.  The window runs as many whole jobs as
fill about ``--seconds``; the rate is all the units of those jobs over
all their time.  A sample of the jobs, drawn from the seed,
keeps its answer for the check after the window.
"""
from __future__ import annotations

import time

import jax
import numpy as np

WARM_JOB = 1 << 30        # fold_in index of the warm-up job's inputs
CHECKED_JOBS = 2          # answers kept for the check after the window


class Driver:
    def __init__(self, *, op, cfg: dict, traffic: dict, seed: int, root):
        self.op, self.cfg, self.traffic = op, cfg, traffic
        self.seed, self.root = seed, root
        self.args = op.job_args(cfg, traffic["job"])
        self.jobs: list = []
        self.kept: dict = {}

    def _inputs(self, i: int):
        inputs = self.op.make_input(jax.random.fold_in(self.root, i),
                                    **self.args["make"])
        return jax.block_until_ready(inputs)

    def setup(self, seconds: float) -> None:
        """One job at the window's shapes, on inputs of its own: it
        compiles or loads every program the window runs."""
        answer, _ = self.op.call(self._inputs(WARM_JOB), **self.args["call"])
        del answer

    def window(self, seconds: float) -> None:
        """Whole jobs back to back, as many as fill about ``seconds`` of
        wall time: the next starts while the last job's time, added,
        would end the window less than half a job past ``seconds``."""
        rng = np.random.default_rng([self.seed, 1])
        start, last, i = time.perf_counter(), 0.0, 0
        while i == 0 or time.perf_counter() - start + last / 2 <= seconds:
            inputs = self._inputs(i)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.job", job=i):
                answer, report = self.op.call(inputs, **self.args["call"])
            dt = time.perf_counter() - t0
            last = dt
            self.jobs.append({
                "job": i, "seconds": dt,
                "units": self.op.units(inputs, answer, report),
                "k_workload": (None if report is None
                               else float(report.k_workload)),
                **self.op.guarantees(report, **self.args["bounds"])})
            # reservoir sample of the answers, drawn from the seed
            slot = i if i < CHECKED_JOBS else int(rng.integers(0, i + 1))
            if slot < CHECKED_JOBS:
                self.kept[slot] = (i, self.op.keep(answer))
            del inputs, answer, report
            i += 1

    def free(self) -> None:
        pass

    def verify(self) -> dict:
        """The numbers compared: wrong answers summed over the checked
        jobs, guarantees at their worst over every job."""
        out = {}
        for i, kept in sorted(self.kept.values(), key=lambda p: p[0]):
            host = self.op.regenerate(jax.random.fold_in(self.root, i),
                                      **self.args["make"])
            for name, value in self.op.check(host, kept).items():
                out[name] = out.get(name, 0) + value
            del host
        for name in ("rounds_over", "workload_over_bound"):
            values = [j[name] for j in self.jobs]
            out[name] = (None if not values or None in values
                         else max(values))
        out["checked"] = len(self.kept)
        return out
