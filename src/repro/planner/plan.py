"""Plan selection + the plan cache — the planner's front half.

``plan_sort_query`` / ``plan_join_query`` run the sketch pass on a
substrate, score every candidate through the cost model, and return a
:class:`QueryPlan`.  Plans are cached under a **shard fingerprint** — a
content hash of (dtype, shape, bytes) of the inputs plus the query
parameters — so a repeated query over the same data skips the sketch
pass entirely.  Content-addressed keys make invalidation trivial:
changed data hashes to a different key, so a stale entry can never be
served; the cache is a bounded LRU (``PLAN_CACHE_MAX`` entries) and the
oldest plans simply fall out.

``planner_stats()`` exposes sketch-run / cache-hit counters so tests
and benchmarks can prove the cache actually short-circuits the sketch.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import threading
from typing import Dict, Optional

import numpy as np

from repro.cluster.substrate import Substrate, VmapSubstrate
from repro.obs import trace as obs_trace

from .cost import (CostEstimate, choose_exchange, join_costs,
                   moe_dispatch_costs, select, select_dispatch, sort_costs)
from .sketch import (expert_counts_estimate, profile_join_tables,
                     profile_sorted_shards, sketch_table)

__all__ = [
    "QueryPlan", "fingerprint_arrays", "plan_sort_query", "plan_join_query",
    "plan_moe_query", "clear_plan_cache", "planner_stats", "PLAN_CACHE_MAX",
]

PLAN_CACHE_MAX = 128

_PLAN_CACHE: "collections.OrderedDict[str, QueryPlan]" = \
    collections.OrderedDict()
_STATS = collections.Counter()
# The plan cache is shared by every thread the serving engine runs; the
# OrderedDict move_to_end/popitem pair and the stats counters are
# read-modify-write, so all access goes through one lock (RLock: the
# plan_* functions tick stats while holding it).
_LOCK = threading.RLock()


@dataclasses.dataclass
class QueryPlan:
    """One planning decision: profile, all candidate costs, the winner."""
    kind: str                        # "sort" | "join"
    algorithm: str                   # the chosen JOIN_/SORT_ALGORITHMS entry
    t: int
    fingerprint: str
    predicted: CostEstimate          # candidates[algorithm]
    candidates: Dict[str, CostEstimate]
    profile: object                  # TableProfile | DataProfile
    cached: bool = False             # served from the plan cache
    exchange: str = "flat"           # shuffle topology ("flat" | "staged")
    exchange_costs: Optional[Dict] = None   # choose_exchange details

    def summary(self) -> str:
        ranked = sorted(self.candidates.values(), key=lambda c: c.score)
        lines = [f"plan[{self.kind}] -> {self.algorithm}"
                 f" (exchange={self.exchange}, cached={self.cached}, "
                 f"fp={self.fingerprint[:12]})"]
        for c in ranked:
            mark = "*" if c.algorithm == self.algorithm else " "
            lines.append(
                f"  {mark} {c.algorithm:11s} alpha={c.alpha} "
                f"k_w={c.k_workload:6.2f} k_n={c.k_network:6.2f} "
                f"recv={c.peak_receive:10.0f} "
                f"bytes={c.bytes_shuffled:12.0f}"
                + ("" if c.feasible else "  [infeasible]"))
        return "\n".join(lines)


def fingerprint_arrays(*arrays, extra: str = "") -> str:
    """Content hash of (dtype, shape, bytes) per array + query params."""
    h = hashlib.blake2b(digest_size=16)
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    h.update(extra.encode())
    return h.hexdigest()


def clear_plan_cache() -> None:
    with _LOCK:
        _PLAN_CACHE.clear()
        _STATS.clear()


def planner_stats() -> Dict[str, int]:
    """Counters: sketch_runs, cache_hits, cache_misses, cache_evictions."""
    with _LOCK:
        return dict(_STATS)


def _tick(counter: str, n: int = 1) -> None:
    with _LOCK:
        _STATS[counter] += n


def _cache_get(key: str) -> Optional[QueryPlan]:
    with _LOCK:
        plan = _PLAN_CACHE.get(key)
        if plan is None:
            _STATS["cache_misses"] += 1
            return None
        _PLAN_CACHE.move_to_end(key)
        _STATS["cache_hits"] += 1
        return dataclasses.replace(plan, cached=True)


def _cache_put(key: str, plan: QueryPlan) -> None:
    with _LOCK:
        _PLAN_CACHE[key] = plan
        while len(_PLAN_CACHE) > PLAN_CACHE_MAX:
            _PLAN_CACHE.popitem(last=False)
            _STATS["cache_evictions"] += 1


@functools.lru_cache(maxsize=32)
def _sketch_substrate(t: int) -> VmapSubstrate:
    """One jit-compiling vmap substrate per machine count — the compiled
    sketch program is cached inside it, so repeated plans over same-shaped
    (but different) data pay eager dispatch exactly once."""
    return VmapSubstrate(t, jit=True)


def plan_sort_query(x, *, t: int, r: int = 2,
                    substrate: Optional[Substrate] = None):
    """Sketch -> score -> choose for ``cluster.sort(algorithm="auto")``.

    Returns ``(QueryPlan, sketch_phases)``; the phases are [] on a
    cache hit (no sketch ran)."""
    key = fingerprint_arrays(x, extra=f"sort|t={t}|r={r}")
    with obs_trace.span("plan.sort", t=t):
        plan = _cache_get(key)
        if plan is not None:
            obs_trace.event("plan.cache_hit", fingerprint=key[:12])
            return plan, []
        sub = substrate if (substrate is not None and substrate.t == t
                            and len(substrate.axes) == 1) \
            else _sketch_substrate(t)
        _tick("sketch_runs")
        with obs_trace.span("planner.sketch"):
            profile, tape = profile_sorted_shards(x, sub)
        with obs_trace.span("planner.score"):
            costs = sort_costs(profile, t, r=r)
            chosen = select(costs)
            m = max(1, profile.n // t)
            topology, ex_costs = choose_exchange(
                t, m, algorithm=chosen.algorithm, r=r)
        plan = QueryPlan(kind="sort", algorithm=chosen.algorithm, t=t,
                         fingerprint=key, predicted=chosen, candidates=costs,
                         profile=profile, exchange=topology,
                         exchange_costs=ex_costs)
        _cache_put(key, plan)
        return plan, tape.phases(t)


def plan_join_query(s_keys, t_keys, *, t_machines: int,
                    mem_budget: Optional[int] = None,
                    substrate: Optional[Substrate] = None):
    """Sketch -> score -> choose for ``cluster.join(algorithm="auto")``.

    Returns ``(QueryPlan, sketch_phases)``."""
    from repro.core.localjoin import MASKED_KEY

    t = t_machines
    key = fingerprint_arrays(s_keys, t_keys,
                             extra=f"join|t={t}|mem={mem_budget}")
    with obs_trace.span("plan.join", t=t):
        plan = _cache_get(key)
        if plan is not None:
            obs_trace.event("plan.cache_hit", fingerprint=key[:12])
            return plan, []
        sub = substrate if (substrate is not None and substrate.t == t
                            and len(substrate.axes) == 1) \
            else _sketch_substrate(t)
        _tick("sketch_runs")
        s32 = np.asarray(s_keys, np.int32)
        t32 = np.asarray(t_keys, np.int32)
        with obs_trace.span("planner.sketch"):
            profile, tape = profile_join_tables(
                s32, t32, t, sub, masked=int(MASKED_KEY))
        with obs_trace.span("planner.score"):
            costs = join_costs(profile, t, mem_budget=mem_budget)
            chosen = select(costs)
        plan = QueryPlan(kind="join", algorithm=chosen.algorithm, t=t,
                         fingerprint=key, predicted=chosen, candidates=costs,
                         profile=profile)
        _cache_put(key, plan)
        return plan, tape.phases(t)


def plan_moe_query(x, router, *, t_machines: int, num_experts: int,
                   top_k: int, extra_slots: int,
                   capacity_factor: float = 1.25,
                   substrate: Optional[Substrate] = None):
    """Sketch -> score -> choose for ``cluster.moe_dispatch(mode="auto")``.

    The sketched table is the router's top-k expert-id stream — routing
    IS a join keyed by expert id, so the same heavy-hitter/CountMin
    machinery that prices skew joins prices dispatch skew.  Returns
    ``(QueryPlan, sketch_phases)``; ``plan.profile`` is the id
    TableProfile, and per-expert counts are re-derived from it via
    :func:`expert_counts_estimate` (nothing MoE-specific is cached).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    t = t_machines
    key = fingerprint_arrays(
        x, router,
        extra=f"moe|t={t}|e={num_experts}|k={top_k}|r={extra_slots}"
              f"|cf={capacity_factor}")
    with obs_trace.span("plan.moe", t=t):
        plan = _cache_get(key)
        phases = []
        if plan is None:
            sub = substrate if (substrate is not None and substrate.t == t
                                and len(substrate.axes) == 1) \
                else _sketch_substrate(t)
            _tick("sketch_runs")
            with obs_trace.span("planner.sketch"):
                # Exactly the dispatch body's routing expression (vmapped
                # einsum + top_k in f32) so the sketched ids ARE the
                # runtime ids.
                xr = jnp.asarray(x).reshape(t, -1, x.shape[-1])
                ids = jax.vmap(
                    lambda xl: lax.top_k(
                        jnp.einsum("md,de->me", xl.astype(jnp.float32),
                                   jnp.asarray(router)), top_k)[1])(xr)
                ids = ids.reshape(t, -1).astype(jnp.int32)
                profile, tape = sketch_table(ids, sub, sample=None)
            with obs_trace.span("planner.score"):
                tokens = ids.shape[0] * ids.shape[1] // top_k
                counts = expert_counts_estimate(profile, num_experts)
                costs = moe_dispatch_costs(
                    counts, tokens=tokens, top_k=top_k,
                    num_experts=num_experts, extra_slots=extra_slots,
                    t_machines=t, capacity_factor=capacity_factor)
                chosen = select_dispatch(costs)
            plan = QueryPlan(kind="moe", algorithm=chosen.algorithm, t=t,
                             fingerprint=key, predicted=chosen,
                             candidates=costs, profile=profile)
            _cache_put(key, plan)
            phases = tape.phases(t)
        else:
            obs_trace.event("plan.cache_hit", fingerprint=key[:12])
    return plan, phases
