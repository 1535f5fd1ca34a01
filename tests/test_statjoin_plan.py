"""StatJoin's array planner and vectorised routing against the
object-per-rectangle implementation they replaced, kept below verbatim as
the oracle: the plan's rows (in order) and both routed index tensors must
be bitwise equal, so the compiled program's shapes and the pairs each
machine joins never change."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path
from typing import List, Tuple

import numpy as np
import pytest

from repro.core.statjoin import (JoinStatistics, Rectangle, StatJoinPlan,
                                 _routing_tensors, collect_statistics,
                                 plan_statjoin)
from repro.core.alpha_k import statjoin_workload_bound

from _prop import given, settings, st

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


# ---------------------------------------------------------------------------
# the oracle: the planner and routing as they were, one object per rectangle
# ---------------------------------------------------------------------------

def _oracle_plan(stats: JoinStatistics, t: int) -> List[Rectangle]:
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


def _oracle_routing(keys: np.ndarray, rects: List[Rectangle], t: int,
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


# ---------------------------------------------------------------------------
# the differential check
# ---------------------------------------------------------------------------

FIELDS = ("key", "s_lo", "s_hi", "t_lo", "t_hi", "machine")


def assert_same_plan_and_routing(stats, t, s_keys, t_keys):
    """Plan rows in order, and both routed tensors, bitwise equal to the
    oracle's; returns the array plan."""
    plan = plan_statjoin(stats, t)
    want = _oracle_plan(stats, t)
    assert isinstance(plan, StatJoinPlan) and len(plan) == len(want)
    for f in FIELDS:
        col = getattr(plan, f)
        assert col.dtype == np.int64, f
        np.testing.assert_array_equal(
            col, np.array([getattr(r, f) for r in want], np.int64), f)
    assert list(plan) == want
    for side, keys in (("s", s_keys), ("t", t_keys)):
        keys = np.asarray(keys, np.int32)
        got, got_cap = _routing_tensors(keys, plan, t, side)
        ref, ref_cap = _oracle_routing(keys, want, t, side)
        assert got_cap == ref_cap and got.dtype == ref.dtype, side
        np.testing.assert_array_equal(got, ref, side)
    return plan


def _loads(plan: StatJoinPlan, t: int) -> np.ndarray:
    sizes = (plan.s_hi - plan.s_lo) * (plan.t_hi - plan.t_lo)
    return np.bincount(plan.machine, weights=sizes, minlength=t)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 16))
def test_property_random_statistics_match_oracle(seed, t):
    """Random tables (skewed S, keys negative too, some keys in one table
    only) at t = 2..16."""
    rng = np.random.default_rng(seed)
    ns, nt = int(rng.integers(0, 400)), int(rng.integers(0, 400))
    domain = int(rng.integers(1, 60))
    s_keys = (rng.zipf(1.3, ns) % domain - 5).astype(np.int32)
    t_keys = rng.integers(-5, domain, nt).astype(np.int32)
    stats = collect_statistics(s_keys, t_keys)
    assert_same_plan_and_routing(stats, t, s_keys, t_keys)


def _tables(stats: JoinStatistics, rng) -> Tuple[np.ndarray, np.ndarray]:
    """Shuffled tables whose statistics are ``stats``."""
    s_keys = rng.permutation(np.repeat(stats.keys, stats.m)).astype(np.int32)
    t_keys = rng.permutation(np.repeat(stats.keys, stats.n)).astype(np.int32)
    return s_keys, t_keys


@pytest.mark.parametrize("name,keys,m,n,t", [
    # MN * t == j * W with W/t = 21/5 not a binary float: no residual
    ("exact_multiple", [7], [21], [1], 5),
    ("exact_multiple_with_smalls", [7, 8, 9], [20, 1, 1], [1, 1, 1], 11),
    # big keys split along S (M >= N) and along T (N > M), with smalls
    ("big_both_sides", [3, 5, 11, 12, 13, 14], [40, 2, 1, 3, 1, 2],
     [3, 50, 1, 1, 2, 1], 6),
    ("four_big_keys", [1, 2, 3, 4, 5], [30, 1, 25, 2, 1],
     [1, 30, 2, 25, 1], 8),
    # one machine: every result is small, all to machine 0
    ("one_machine", [1, 2, 3], [4, 1, 2], [2, 3, 1], 1),
    ("one_machine_one_key", [9], [6], [5], 1),
])
def test_constructed_statistics_match_oracle(name, keys, m, n, t, rng):
    stats = JoinStatistics(np.array(keys, np.int32), np.array(m),
                           np.array(n))
    s_keys, t_keys = _tables(stats, rng)
    plan = assert_same_plan_and_routing(stats, t, s_keys, t_keys)
    loads = _loads(plan, t)
    assert loads.sum() == stats.total
    assert loads.max() * t <= 2 * stats.total


def test_empty_join_gives_empty_plan_and_padding_only():
    """W = 0: no rectangles, and every machine's row is one pad."""
    s_keys = np.array([1, 2, 2], np.int32)
    t_keys = np.array([3, 4], np.int32)
    stats = collect_statistics(s_keys, t_keys)
    assert stats.total == 0
    plan = assert_same_plan_and_routing(stats, 4, s_keys, t_keys)
    assert len(plan) == 0 and list(plan) == []
    idx, cap = _routing_tensors(s_keys, plan, 4, "s")
    assert cap == 1 and (idx == -1).all() and idx.shape == (4, 1)


def test_plan_key_absent_from_a_table_is_skipped(rng):
    """Statistics that name keys a table lacks (stale or foreign
    statistics): those rectangles route nothing from that table."""
    stats = JoinStatistics(np.array([2, 5, 8, 40], np.int32),
                           np.array([6, 1, 3, 2]), np.array([1, 4, 2, 2]))
    s_keys, t_keys = _tables(stats, rng)
    s_keys = s_keys[s_keys != 5]           # key 5 absent from S
    t_keys = np.where(t_keys == 40, 41, t_keys).astype(np.int32)
    assert_same_plan_and_routing(stats, 3, s_keys, t_keys)
    assert_same_plan_and_routing(stats, 3, s_keys, t_keys[:0])


@pytest.mark.parametrize("t", [2, 3, 7, 16])
def test_equal_small_results_go_round_robin_from_machine_zero(t):
    """Ties go to the lowest machine, as np.argmin's first minimum."""
    k = 5 * t + 3
    stats = JoinStatistics(np.arange(k), np.ones(k, np.int64),
                           np.ones(k, np.int64))
    plan = plan_statjoin(stats, t)
    np.testing.assert_array_equal(plan.machine, np.arange(k) % t)
    np.testing.assert_array_equal(plan.key, np.arange(k))


def test_greedy_fills_the_lightest_machine_first():
    """W = 15, t = 4: key 0 (MN = 10) is cut into rows [0,4) [4,7) [7,10)
    of S; the first two take fresh machines 0 and 1, and the residual and
    the five unit results then go to the least-loaded, lowest machine."""
    stats = JoinStatistics(np.arange(6), np.array([10, 1, 1, 1, 1, 1]),
                           np.array([1, 1, 1, 1, 1, 1]))
    plan = plan_statjoin(stats, 4)
    assert list(plan)[0] == Rectangle(0, 0, 4, 0, 1, 0)
    assert [r.machine for r in plan] == [0, 1, 2, 3, 3, 3, 1, 2]
    assert list(plan)[2] == Rectangle(0, 7, 10, 0, 1, 2)
    assert _loads(plan, 4).max() * 4 <= 2 * stats.total


def test_fk_join_rehearsal_size_matches_oracle():
    """The benchmark's fk-join input at its rehearsal size, 2^19 x 2^17,
    Zipf 1.1 foreign keys, t = 16."""
    from bench import data
    s_keys, _, t_keys, _ = (np.asarray(a) for a in data.fk_join_input(
        data.root_key(2**31 + 77), data.root_key(0), n_s=2**19, n_t=2**17,
        exponent=1.1))
    stats = collect_statistics(s_keys, t_keys)
    plan = assert_same_plan_and_routing(stats, 16, s_keys, t_keys)
    hot = stats.keys[np.argmax(stats.sizes)]
    assert np.count_nonzero(plan.key == hot) > 1  # the hot key is split
    loads = _loads(plan, 16)
    assert loads.sum() == stats.total
    assert loads.max() <= statjoin_workload_bound(stats.total, 16)
