"""StatJoin (paper §4.3) — deterministic skew equi-join via statistics.

Rounds 1-2: parallel-sort S and T by join key (SMMS), collecting per-key
counts (M_k, N_k) — the "statistics".  Round 3: a *deterministic* planner
maps join results to machines, tuples are routed per plan, and each
machine cross-products what it receives.

Planner (faithful to §4.3.2-4.3.3):
  * W = total join size; a key's result is **big** if M_k * N_k > W/t.
  * A big result with (j-1) W/t < MN <= j W/t is cut into j *mapping
    rectangles* along its longer side, as evenly as possible; the j-1
    largest go to fresh machines (each machine gets at most one big
    rectangle), the smallest (*residual*) joins the small pool when
    MN < j W/t.  All comparisons against the W/t threshold are done in
    exact integer arithmetic (MN * t vs j * W) — W/t is a float whose
    rounding would misclassify exact multiples.
  * Small results (and residuals) go one-by-one to the currently
    least-loaded machine.

Theorem 6: every machine's output <= 2 W / t — deterministically.  That
bound is the static output-buffer capacity on TPU.

Execution model mirrors the paper's MapReduce layout: the planner runs on
tiny per-key statistics (the paper puts it in the map *setup* function —
host-side here); tuple routing + the cross product are device code run on
a repro.cluster substrate, with the route/stat phases recorded on the
CollectiveTape (received counts measured in-program from the landed
fragments).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.collectives import CollectiveTape
from repro.cluster.substrate import Substrate, default_pool
from repro.obs import trace as obs_trace

from .localjoin import MASKED_KEY, local_equijoin
from .alpha_k import statjoin_workload_bound

__all__ = [
    "JoinStatistics", "Rectangle", "collect_statistics", "plan_statjoin",
    "statjoin",
]


@dataclasses.dataclass(frozen=True)
class JoinStatistics:
    keys: np.ndarray   # (k,) join keys present in both tables
    m: np.ndarray      # (k,) multiplicity in S
    n: np.ndarray      # (k,) multiplicity in T

    @property
    def sizes(self) -> np.ndarray:
        return self.m.astype(np.int64) * self.n.astype(np.int64)

    @property
    def total(self) -> int:
        return int(self.sizes.sum())


@dataclasses.dataclass(frozen=True)
class Rectangle:
    """One result-to-machine mapping entry: key x [s_lo,s_hi) x [t_lo,t_hi)."""
    key: int
    s_lo: int
    s_hi: int
    t_lo: int
    t_hi: int
    machine: int

    @property
    def size(self) -> int:
        return (self.s_hi - self.s_lo) * (self.t_hi - self.t_lo)


def collect_statistics(s_keys: np.ndarray, t_keys: np.ndarray
                       ) -> JoinStatistics:
    """Per-key multiplicities for keys present in both tables."""
    with obs_trace.span("statjoin.statistics"):
        ks, cs = np.unique(s_keys, return_counts=True)
        kt, ct = np.unique(t_keys, return_counts=True)
        common, is_, it_ = np.intersect1d(ks, kt, return_indices=True)
        return JoinStatistics(common, cs[is_], ct[it_])


def plan_statjoin(stats: JoinStatistics, t: int) -> List[Rectangle]:
    """§4.3.2/4.3.3 planner.  Returns the result-to-machine mapping."""
    with obs_trace.span("statjoin.plan"):
        return _plan(stats, t)


def _plan(stats: JoinStatistics, t: int) -> List[Rectangle]:
    w = stats.total
    if w == 0:
        return []
    # Exact integer comparisons against the threshold W/t: MN > W/t iff
    # MN * t > W.  (float W/t misclassifies exact multiples, e.g.
    # MN = 21, W/t = 21/5: 5 * (21/5.) != 21.0 in binary floats.)
    big_mask = stats.sizes * t > w

    rects: List[Rectangle] = []
    small_pool: List[Rectangle] = []  # machine=-1 until placed
    next_machine = 0
    loads = np.zeros(t, dtype=np.int64)

    # ---- big results: split along the longer side into j rectangles -------
    for key, m_k, n_k in zip(stats.keys[big_mask], stats.m[big_mask],
                             stats.n[big_mask]):
        mn = int(m_k) * int(n_k)
        j = -(-mn * t // w)  # ceil(MN / (W/t)) in exact integers
        split_s = m_k >= n_k
        longer = int(m_k if split_s else n_k)
        j = min(j, longer)  # cannot split finer than one tuple per interval
        base, extra = divmod(longer, j)
        # interval sizes (desc): 'extra' intervals of base+1, rest of base
        pieces = []
        lo = 0
        for p in range(j):
            size = base + (1 if p < extra else 0)
            pieces.append((lo, lo + size))
            lo += size
        pieces.sort(key=lambda ab: ab[1] - ab[0], reverse=True)
        exact = mn * t == j * w  # MN == j * W/t, exactly
        assigned = pieces if exact else pieces[:-1]
        residual = None if exact else pieces[-1]
        for (plo, phi) in assigned:
            r = (Rectangle(int(key), plo, phi, 0, int(n_k), next_machine)
                 if split_s else
                 Rectangle(int(key), 0, int(m_k), plo, phi, next_machine))
            if next_machine < t:
                rects.append(r)
                loads[next_machine] += r.size
                next_machine += 1
            else:  # cannot happen when sum(j_B - 1) <= t; guard anyway
                small_pool.append(dataclasses.replace(r, machine=-1))
        if residual is not None:
            plo, phi = residual
            r = (Rectangle(int(key), plo, phi, 0, int(n_k), -1) if split_s
                 else Rectangle(int(key), 0, int(m_k), plo, phi, -1))
            small_pool.append(r)

    # ---- small results -----------------------------------------------------
    for key, m_k, n_k in zip(stats.keys[~big_mask], stats.m[~big_mask],
                             stats.n[~big_mask]):
        small_pool.append(Rectangle(int(key), 0, int(m_k), 0, int(n_k), -1))

    # greedy: next small result to the least-loaded machine (§4.3.3)
    for r in small_pool:
        machine = int(np.argmin(loads))
        rects.append(dataclasses.replace(r, machine=machine))
        loads[machine] += r.size
    return rects


def _routing_tensors(keys: np.ndarray, rects: List[Rectangle], t: int,
                     side: str) -> Tuple[np.ndarray, int]:
    """Per-machine padded index lists of table rows, per the plan.

    keys: the table's key column.  side: 's' or 't' picks the rect range.
    """
    order = np.argsort(keys, kind="stable")  # ranks within key group
    sorted_keys = keys[order]
    group_start = {}
    uk, first = np.unique(sorted_keys, return_index=True)
    for k, f in zip(uk, first):
        group_start[int(k)] = int(f)

    per_machine: List[List[np.ndarray]] = [[] for _ in range(t)]
    for r in rects:
        lo, hi = (r.s_lo, r.s_hi) if side == "s" else (r.t_lo, r.t_hi)
        base = group_start.get(r.key)
        if base is None or hi <= lo:
            continue
        per_machine[r.machine].append(order[base + lo: base + hi])

    cap = max(1, max((sum(len(a) for a in lst) for lst in per_machine),
                     default=1))
    out = np.full((t, cap), -1, dtype=np.int64)
    for i, lst in enumerate(per_machine):
        if lst:
            idx = np.concatenate(lst)
            out[i, :len(idx)] = idx
    return out, cap


def _statjoin_body(a, b, c, d, *, tape, n_in, n_stat, t, capacity,
                   kernel_backend):
    """Per-device StatJoin body (module-level: a functools.partial of this
    keys the substrate's compiled-program cache on content)."""
    # Rounds 1-2: the SMMS sort that produced the statistics — each
    # tuple crosses the network once (n/t per machine, paper §4.3.1).
    with tape.phase("rounds1-2 sort+stats"):
        tape.record(sent=n_in / t, received=n_in / t)
    # Round 3a: every machine learns the tiny per-key statistics so it
    # can run the (deterministic, replicated) planner.
    with tape.phase("round3 stats->plan"):
        tape.record(sent=n_stat, received=n_stat)
    # Round 3b: tuples routed per plan; the received count is measured
    # in-program from the landed fragments (replicated tuples count
    # once per copy — that is the paper's network cost of rectangles).
    with tape.phase("round3 route"):
        received = (jnp.sum(a != MASKED_KEY) + jnp.sum(c != MASKED_KEY))
        tape.record(sent=n_in / t, received=received)
        return local_equijoin(a, b, c, d, capacity,
                              kernel_backend=kernel_backend)


def statjoin(s_keys: np.ndarray, s_rows: np.ndarray,
             t_keys: np.ndarray, t_rows: np.ndarray,
             t_machines: int, out_cap_factor: float = 1.05,
             stats: Optional[JoinStatistics] = None,
             kernel_backend: Optional[str] = None,
             substrate: Optional[Substrate] = None,
             out_capacity: Optional[int] = None,
             donate: Optional[bool] = None):
    """Host wrapper: plan on statistics, execute per machine on a substrate.

    out_capacity overrides the Theorem-6-derived per-machine output
    buffer (ceil(out_cap_factor * 2W/t)) when given.

    ``donate=None`` (default) donates the four routed fragment tensors
    to the compiled program: StatJoin's capacity schedule is single-shot
    by construction (the plan is exact, there is no retry loop) and the
    fragments are built fresh in this call, so nothing can re-read
    them.  ``donate=False`` keeps them alive (dropped anyway on
    platforms without donation support — see
    ``Substrate.stats['donation_dropped']``).
    """
    t = t_machines
    s_keys = np.asarray(s_keys, np.int32)
    t_keys = np.asarray(t_keys, np.int32)
    if stats is None:
        stats = collect_statistics(s_keys, t_keys)
    rects = plan_statjoin(stats, t)
    w = stats.total
    if substrate is None:
        substrate = default_pool()(t)
    assert substrate.t == t, (substrate, t)

    def frag(keys, rows, idx):
        k = np.where(idx >= 0, keys[np.clip(idx, 0, len(keys) - 1)],
                     MASKED_KEY).astype(np.int32)
        v = np.where(idx >= 0, rows[np.clip(idx, 0, len(rows) - 1)], 0)
        return jnp.asarray(k), jnp.asarray(v.astype(np.int32))

    # everything that turns the plan into device operands
    with obs_trace.span("statjoin.route"):
        s_idx, _ = _routing_tensors(s_keys, rects, t, "s")
        t_idx, _ = _routing_tensors(t_keys, rects, t, "t")
        sk, sr = frag(s_keys, np.asarray(s_rows), s_idx)
        tk, tr = frag(t_keys, np.asarray(t_rows), t_idx)

    capacity = (int(out_capacity) if out_capacity is not None
                else max(1, math.ceil(
                    out_cap_factor * statjoin_workload_bound(w, t))))
    n_in = len(s_keys) + len(t_keys)
    n_stat = len(stats.keys)

    body = functools.partial(_statjoin_body, n_in=n_in, n_stat=n_stat, t=t,
                             capacity=capacity,
                             kernel_backend=kernel_backend)
    donate_argnums = (0, 1, 2, 3) if donate is not False else ()
    out, tape = substrate.run(body, sk, sr, tk, tr,
                              donate_argnums=donate_argnums)

    counts = np.asarray(out.count).reshape(-1)
    report = tape.report(algorithm="StatJoin", t=t, n_in=n_in, n_out=w,
                         workload=counts)
    report.theoretical_workload_bound = statjoin_workload_bound(w, t)
    report.plan = rects
    return out, report
