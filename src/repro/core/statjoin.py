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

The plan is a struct of arrays (:class:`StatJoinPlan`), one row per
rectangle, from the planner through routing to ``report.plan``; it
iterates as :class:`Rectangle` objects for callers that want them.  Only
the big keys (fewer than t) are planned in a Python loop.  The greedy is
a heap of (load, machine) pairs of Python ints: its least pair is the
lowest-index machine among the least loaded, the first minimum that
``np.argmin(loads)`` would pick, so ties go to the lowest machine and
equal small results go round robin from machine 0.  Routing sorts each
table once by (key, row) packed into an int64, finds each rectangle's
key group by ``searchsorted``, and lays the rectangles' rows out per
machine with ``np.repeat``, in plan order within each machine.

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
import heapq
import math
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.collectives import CollectiveTape
from repro.cluster.substrate import Substrate, default_pool
from repro.obs import trace as obs_trace

from .localjoin import MASKED_KEY, local_equijoin
from .alpha_k import statjoin_workload_bound

__all__ = [
    "JoinStatistics", "Rectangle", "StatJoinPlan", "collect_statistics",
    "plan_statjoin", "statjoin",
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


@dataclasses.dataclass(frozen=True)
class StatJoinPlan:
    """The result-to-machine mapping as a struct of int64 arrays: row i
    is the rectangle ``key[i] x [s_lo[i], s_hi[i]) x [t_lo[i], t_hi[i])``
    on ``machine[i]``.

    Rows are in planning order: the big rectangles placed on fresh
    machines, then the small pool in placement order (big keys'
    residuals, then the small keys in statistics order).  Iterating
    yields the rows as :class:`Rectangle`s."""
    key: np.ndarray
    s_lo: np.ndarray
    s_hi: np.ndarray
    t_lo: np.ndarray
    t_hi: np.ndarray
    machine: np.ndarray

    def __len__(self) -> int:
        return len(self.key)

    def __iter__(self) -> Iterator[Rectangle]:
        cols = (self.key, self.s_lo, self.s_hi, self.t_lo, self.t_hi,
                self.machine)
        return (Rectangle(*row) for row in zip(*(c.tolist() for c in cols)))


def plan_statjoin(stats: JoinStatistics, t: int) -> StatJoinPlan:
    """§4.3.2/4.3.3 planner.  Returns the result-to-machine mapping."""
    with obs_trace.span("statjoin.plan"):
        return _plan(stats, t)


def _plan(stats: JoinStatistics, t: int) -> StatJoinPlan:
    w = stats.total
    if w == 0:
        return StatJoinPlan(*(np.zeros(0, np.int64) for _ in range(6)))
    # Exact integer comparisons against the threshold W/t: MN > W/t iff
    # MN * t > W.  (float W/t misclassifies exact multiples, e.g.
    # MN = 21, W/t = 21/5: 5 * (21/5.) != 21.0 in binary floats.)
    big_mask = stats.sizes * t > w

    placed = []  # (key, s_lo, s_hi, t_lo, t_hi, machine) on fresh machines
    pool = []    # big keys' residuals (and overflow), placed by the greedy
    loads = [0] * t

    # ---- big results (fewer than t): split along the longer side -----------
    for key, m_k, n_k in zip(stats.keys[big_mask].tolist(),
                             stats.m[big_mask].tolist(),
                             stats.n[big_mask].tolist()):
        mn = m_k * n_k
        j = -(-mn * t // w)  # ceil(MN / (W/t)) in exact integers
        split_s = m_k >= n_k
        longer = m_k if split_s else n_k
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
        residual = None if exact else pieces.pop()
        for plo, phi in pieces:
            rect = ((key, plo, phi, 0, n_k) if split_s
                    else (key, 0, m_k, plo, phi))
            if len(placed) < t:
                loads[len(placed)] = (phi - plo) * (n_k if split_s else m_k)
                placed.append(rect + (len(placed),))
            else:  # cannot happen when sum(j_B - 1) <= t; guard anyway
                pool.append(rect)
        if residual is not None:
            plo, phi = residual
            pool.append((key, plo, phi, 0, n_k) if split_s
                        else (key, 0, m_k, plo, phi))

    # ---- small results, as columns after the residuals ---------------------
    small = ~big_mask
    m_s = stats.m[small].astype(np.int64)
    n_s = stats.n[small].astype(np.int64)
    zeros = np.zeros(len(m_s), np.int64)
    cols = [np.concatenate((c, x)) for c, x in zip(
        np.asarray(pool, np.int64).reshape(-1, 5).T,
        (stats.keys[small].astype(np.int64), zeros, m_s, zeros, n_s))]

    # greedy: next small result to the least-loaded machine (§4.3.3).  The
    # heap's least (load, machine) is np.argmin(loads)'s first minimum.
    heap = [(load, i) for i, load in enumerate(loads)]
    heapq.heapify(heap)
    machines = []
    for size in ((cols[2] - cols[1]) * (cols[4] - cols[3])).tolist():
        load, i = heap[0]
        heapq.heapreplace(heap, (load + size, i))
        machines.append(i)

    big = np.asarray(placed, np.int64).reshape(-1, 6).T
    return StatJoinPlan(*(np.concatenate((b, c)) for b, c in zip(
        big, cols + [np.asarray(machines, np.int64)])))


def _routing_tensors(keys: np.ndarray, plan: StatJoinPlan, t: int,
                     side: str) -> Tuple[np.ndarray, int]:
    """Per-machine padded index lists of table rows, per the plan.

    keys: the table's int32 key column.  side: 's' or 't' picks the
    rectangle range.  Row ``i`` of the result lists, rectangle by
    rectangle in plan order, the rows each of machine ``i``'s rectangles
    takes from its key's group (rows of a group in table order); ``-1``
    pads.
    """
    n = len(keys)
    # one sort of (key, row) packed in an int64: the stable key order
    packed = np.sort((keys.astype(np.int64) << 32) | np.arange(n))
    order = packed & 0xFFFFFFFF
    sorted_keys = packed >> 32
    # a key group starts where the sorted key changes
    first = np.flatnonzero(np.concatenate(
        ([True], sorted_keys[1:] != sorted_keys[:-1]))[:n])
    group_keys = sorted_keys[first]
    at = np.searchsorted(group_keys, plan.key)
    found = at < len(first)
    found[found] = group_keys[at[found]] == plan.key[found]
    lo, hi = (plan.s_lo, plan.s_hi) if side == "s" else (plan.t_lo, plan.t_hi)
    keep = found & (hi > lo)  # keys the table lacks and empty ranges drop
    # order[base + lo : base + hi], clipped at the table's end as a slice
    base = first[at[keep]]
    start = np.minimum(base + lo[keep], n)
    length = np.minimum(base + hi[keep], n) - start
    machine = plan.machine[keep]

    # rectangles grouped by machine, plan order kept within each machine
    by = np.argsort(machine, kind="stable")
    start, length, machine = start[by], length[by], machine[by]
    # the rows of every rectangle, back to back: rectangle r's are
    # rows[edges[r]:edges[r + 1]], machine i's rows[cuts[i]:cuts[i + 1]]
    edges = np.concatenate(([0], np.cumsum(length)))
    src = np.repeat(start - edges[:-1], length)
    src += np.arange(len(src))
    rows = order[src]
    cuts = edges[np.searchsorted(machine, np.arange(t + 1))].tolist()
    cap = max(1, max(b - a for a, b in zip(cuts, cuts[1:])))
    out = np.full((t, cap), -1, dtype=np.int64)
    for i, (a, b) in enumerate(zip(cuts, cuts[1:])):
        out[i, :b - a] = rows[a:b]
    return out, cap


def _statjoin_body(a, b, c, d, *, tape, n_in, n_stat, t, capacity):
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
        return local_equijoin(a, b, c, d, capacity)


def statjoin(s_keys: np.ndarray, s_rows: np.ndarray,
             t_keys: np.ndarray, t_rows: np.ndarray,
             t_machines: int, out_cap_factor: float = 1.05,
             stats: Optional[JoinStatistics] = None,
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
    plan = plan_statjoin(stats, t)
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
        s_idx, _ = _routing_tensors(s_keys, plan, t, "s")
        t_idx, _ = _routing_tensors(t_keys, plan, t, "t")
        sk, sr = frag(s_keys, np.asarray(s_rows), s_idx)
        tk, tr = frag(t_keys, np.asarray(t_rows), t_idx)

    capacity = (int(out_capacity) if out_capacity is not None
                else max(1, math.ceil(
                    out_cap_factor * statjoin_workload_bound(w, t))))
    n_in = len(s_keys) + len(t_keys)
    n_stat = len(stats.keys)

    body = functools.partial(_statjoin_body, n_in=n_in, n_stat=n_stat, t=t,
                             capacity=capacity)
    donate_argnums = (0, 1, 2, 3) if donate is not False else ()
    out, tape = substrate.run(body, sk, sr, tk, tr,
                              donate_argnums=donate_argnums)

    counts = np.asarray(out.count).reshape(-1)
    report = tape.report(algorithm="StatJoin", t=t, n_in=n_in, n_out=w,
                         workload=counts)
    report.theoretical_workload_bound = statjoin_workload_bound(w, t)
    report.plan = plan
    return out, report
