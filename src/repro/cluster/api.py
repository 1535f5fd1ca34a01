"""The cluster front door: one dispatch for all (alpha, k) algorithms.

    from repro import cluster
    (keys, values), report = cluster.sort(x, algorithm="smms")
    out, report = cluster.join(sk, sr, tk, tr, algorithm="statjoin",
                               t_machines=8)

``algorithm="auto"`` hands the choice to the planner (repro.planner):
a one-pass on-device sketch phase profiles the input, the theorem-bound
cost model scores every candidate, and the query dispatches to the
winner — bitwise-identical to calling that algorithm directly.  The
report then carries the chosen :class:`~repro.planner.plan.QueryPlan`
(``report.query_plan``), the predicted (alpha, k)
(``report.predicted_alpha`` / ``report.predicted_k``) next to the
measured ones, and the sketch round's tape entries
(``report.sketch_phases``).  Plans are cached under a shard
fingerprint, so repeating a query over unchanged data skips the sketch.

Every algorithm runs on a Substrate (by default its machines laid over
every chip JAX sees, vmapped on each; a shard_map mesh when requested)
and returns the AlphaKReport assembled from the instrumented
collectives.  Core imports are lazy to keep repro.core -> repro.cluster
-> repro.core import order acyclic.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import numpy as np

from .substrate import Substrate, default_pool

__all__ = ["sort", "join", "moe_dispatch", "SORT_ALGORITHMS",
           "JOIN_ALGORITHMS", "MOE_DISPATCH_MODES", "AUTO"]

SORT_ALGORITHMS = ("smms", "terasort")
JOIN_ALGORITHMS = ("randjoin", "statjoin", "repartition", "broadcast")
MOE_DISPATCH_MODES = ("capacity", "alpha_k", "cluster")
AUTO = "auto"

# ``substrate=`` accepts a Substrate, None, or a *provider* — any
# callable mapping an axis spec to a Substrate (repro.cluster.SubstratePool
# is the canonical one).  A provider lets one object serve every
# algorithm's axis shape: the sorts and 1D joins resolve it with (t,),
# RandJoin with its (a, b) machine matrix — and all queries that agree
# on the axes share one substrate, its lock, and its compiled-program
# cache (the serving engine's cache-sharing contract).  ``None`` is the
# FUSED default: the process-wide jit-compiling pool
# (repro.cluster.default_pool), so every algorithm's multi-round body
# runs as one compiled program reused across calls.  Round-by-round
# execution is still available by passing an eager substrate explicitly
# (``VmapSubstrate(t)`` / ``ShardMapSubstrate(..., jit=False)``).
SubstrateLike = Union[Substrate, "SubstrateProvider", None]


def _resolve_substrate(substrate, *axes) -> Optional[Substrate]:
    if substrate is None:
        substrate = default_pool()
    if isinstance(substrate, Substrate):
        return substrate
    if callable(substrate):
        sub = substrate(*axes)
        if not isinstance(sub, Substrate):
            raise TypeError(f"substrate provider {substrate!r} returned "
                            f"{type(sub).__name__}, expected a Substrate")
        return sub
    raise TypeError(f"substrate must be a Substrate, a provider callable, "
                    f"or None, got {type(substrate).__name__}")


def _attach_plan(report, plan, sketch_phases) -> None:
    """Decorate an AlphaKReport with the planner's decision + prediction."""
    report.query_plan = plan
    report.predicted_alpha = plan.predicted.alpha
    report.predicted_k = plan.predicted.k_workload
    report.predicted_k_network = plan.predicted.k_network
    report.sketch_phases = list(sketch_phases)


def _attach_capacity(report, factor: float, attempts: int) -> None:
    """Make the shared retry loop visible on the report (ServeStats reads
    ``capacity_attempts``; exactly-one-retry == attempts 2)."""
    report.cap_factor = factor
    report.capacity_attempts = attempts


def sort(x, *, algorithm: str = "smms",
         substrate: Optional[Substrate] = None,
         values=None, r: int = 2, seed: int = 0,
         cap_factor: Optional[float] = None,
         backend: str = "static", policy=None, exchange: str = "flat",
         overlap_chunks: int = 2,
         donate: Optional[bool] = None):
    """Distributed sort of x: (t, m).  Returns ((keys, values), report).

    algorithm: one of SORT_ALGORITHMS, or "auto" to let the planner
    sketch the shards and pick (the dispatched call is bitwise-identical
    to naming the winner explicitly).

    exchange: shuffle topology — "flat" (one t-way all_to_all, the
    default), "staged" (two sqrt(t)-way hops over a t1 x t2 factored
    substrate; smaller receive buffers at large t, one extra round), or
    "auto" (the planner's topology model decides from t and the
    predicted receive volume — exactly how ``algorithm="auto"`` picks
    the algorithm).  Sorted output is bitwise-identical across
    topologies; ``report.exchange_topology`` records what actually ran
    (non-factorable t degrades staged to flat with a warning).

    donate: allow the compiled program to consume (reuse) the input
    buffers instead of copying them into the exchange pipeline — do not
    touch ``x``/``values`` afterwards.  ``None`` (the default) donates
    automatically exactly when the resolved capacity schedule is
    single-shot (explicit ``cap_factor`` or a ``policy`` with
    ``max_retries=0``) — retry loops re-run the body from the same
    inputs, so a donated buffer would be gone on attempt 2.  Honored on
    donation-capable platforms (GPU/TPU); dropped otherwise, counted in
    ``Substrate.stats['donation_dropped']`` and the
    ``donation_dropped_total`` metric.
    """
    if np.ndim(x) != 2:
        raise ValueError(
            f"sort expects x of shape (t, m) — one row per machine — got "
            f"shape {np.shape(x)}; reshape with x.reshape(t, -1)")
    t, m = (int(d) for d in np.shape(x))
    if exchange not in ("flat", "staged", AUTO):
        raise ValueError(f"unknown exchange topology {exchange!r}; "
                         f"expected 'flat', 'staged' or '{AUTO}'")
    if algorithm == AUTO:
        from repro.planner import plan_sort_query
        plan, sketch_phases = plan_sort_query(
            x, t=t, r=r, substrate=_resolve_substrate(substrate, t))
        out, report = sort(x, algorithm=plan.algorithm, substrate=substrate,
                           values=values, r=r, seed=seed,
                           cap_factor=cap_factor, backend=backend,
                           policy=policy,
                           exchange=(plan.exchange if exchange == AUTO
                                     else exchange),
                           overlap_chunks=overlap_chunks, donate=donate)
        _attach_plan(report, plan, sketch_phases)
        return out, report
    if exchange == AUTO:
        from repro.planner import choose_exchange
        exchange, _ = choose_exchange(t, m, algorithm=algorithm, r=r,
                                      cap_factor=cap_factor,
                                      overlap_chunks=overlap_chunks)
    # Resolve providers/None with the topology's axis spec; an explicit
    # Substrate instance passes through (the core wrappers reconcile it
    # with the requested topology, warning on impossible combinations).
    if not isinstance(substrate, Substrate):
        from repro.launch.mesh import STAGED_AXIS_NAMES, factor_shards
        fs = factor_shards(t, warn=(exchange == "staged")) \
            if exchange == "staged" else None
        if fs is None:
            substrate = _resolve_substrate(substrate, t)
            exchange = "flat"
        else:
            substrate = _resolve_substrate(
                substrate, (STAGED_AXIS_NAMES[0], fs[0]),
                (STAGED_AXIS_NAMES[1], fs[1]))
    if algorithm == "smms":
        from repro.core.smms import smms_sort
        return smms_sort(x, r=r, cap_factor=cap_factor, values=values,
                         backend=backend, substrate=substrate, policy=policy,
                         exchange=exchange, overlap_chunks=overlap_chunks,
                         donate=donate)
    if algorithm == "terasort":
        from repro.core.terasort import terasort_sort
        if values is not None:
            return terasort_sort(x, seed=seed, cap_factor=cap_factor,
                                 backend=backend, values=values,
                                 substrate=substrate, policy=policy,
                                 exchange=exchange,
                                 overlap_chunks=overlap_chunks,
                                 donate=donate)
        flat, report = terasort_sort(x, seed=seed, cap_factor=cap_factor,
                                     backend=backend,
                                     substrate=substrate, policy=policy,
                                     exchange=exchange,
                                     overlap_chunks=overlap_chunks,
                                     donate=donate)
        return (flat, None), report
    raise ValueError(f"unknown sort algorithm {algorithm!r}; "
                     f"expected one of {SORT_ALGORITHMS + (AUTO,)}")


def join(s_keys, s_rows, t_keys, t_rows, *, algorithm: str = "statjoin",
         t_machines: int, substrate: Optional[Substrate] = None,
         out_capacity: Optional[int] = None, seed: int = 0,
         in_cap_factor: float = 4.0, out_cap_factor: float = 1.05,
         ab: Optional[Tuple[int, int]] = None, stats=None,
         mem_budget: Optional[int] = None, small_side: Optional[str] = None,
         donate: Optional[bool] = None):
    """Distributed equi-join.  Returns (JoinOutput, report).

    algorithm: one of JOIN_ALGORITHMS, or "auto" — sketch both tables in
    one on-device pass, score StatJoin/RandJoin/Broadcast/Repartition
    through the theorem cost model, dispatch to the winner.

    out_capacity defaults to the Theorem-6 bound ceil(2W/t) + slack for
    the algorithms that need an explicit buffer (randjoin/repartition/
    broadcast) — computing W from exact statistics, the same
    information StatJoin's planner uses.  mem_budget caps the broadcast
    small side (planner feasibility, objects); small_side forces the
    broadcast orientation.

    donate: as in :func:`sort` — ``None`` (default) donates the routed
    fragment tensors automatically on the single-shot algorithms
    (statjoin/repartition, whose capacity is planned exactly and never
    retried); ``False`` keeps them alive.  The retrying algorithms
    (randjoin/broadcast under the default capacity) never donate — the
    retry loop re-reads the fragments.
    """
    if algorithm == AUTO:
        from repro.planner import plan_join_query
        plan, sketch_phases = plan_join_query(
            s_keys, t_keys, t_machines=t_machines, mem_budget=mem_budget,
            substrate=_resolve_substrate(substrate, t_machines))
        out, report = join(s_keys, s_rows, t_keys, t_rows,
                           algorithm=plan.algorithm, t_machines=t_machines,
                           substrate=substrate, out_capacity=out_capacity,
                           seed=seed, in_cap_factor=in_cap_factor,
                           out_cap_factor=out_cap_factor, ab=ab, stats=stats,
                           mem_budget=mem_budget, small_side=small_side,
                           donate=donate)
        _attach_plan(report, plan, sketch_phases)
        return out, report
    if algorithm not in JOIN_ALGORITHMS:
        raise ValueError(f"unknown join algorithm {algorithm!r}; "
                         f"expected one of {JOIN_ALGORITHMS + (AUTO,)}")
    if algorithm == "statjoin":
        from repro.core.statjoin import statjoin
        return statjoin(s_keys, s_rows, t_keys, t_rows, t_machines=t_machines,
                        out_cap_factor=out_cap_factor, stats=stats,
                        substrate=_resolve_substrate(substrate, t_machines),
                        out_capacity=out_capacity, donate=donate)

    defaulted_capacity = out_capacity is None
    if defaulted_capacity:
        from repro.core.statjoin import collect_statistics
        st = stats if stats is not None else collect_statistics(
            np.asarray(s_keys, np.int64), np.asarray(t_keys, np.int64))
        w = st.total
        if algorithm == "repartition":
            # the skew-vulnerable baseline can pin the WHOLE result onto
            # one machine — that imbalance is what it exists to exhibit
            out_capacity = w + 64
        else:
            out_capacity = max(64, int(np.ceil(2.0 * out_cap_factor * w
                                               / t_machines)))
    if algorithm == "randjoin":
        from repro.cluster.capacity import CapacityPolicy, run_with_capacity
        from repro.core.randjoin import choose_ab, randjoin
        a, b = ab if ab is not None else choose_ab(
            t_machines, int(np.shape(s_keys)[0]), int(np.shape(t_keys)[0]))
        rj_sub = _resolve_substrate(substrate, ("a", a), ("b", b))

        def attempt_randjoin(cap):
            out, rep = randjoin(s_keys, s_rows, t_keys, t_rows,
                                t_machines=t_machines,
                                out_capacity=int(cap), seed=seed,
                                in_cap_factor=in_cap_factor
                                * (cap / out_capacity),
                                ab=(a, b), substrate=rj_sub)
            return (out, rep), int(np.asarray(out.dropped).max())

        if not defaulted_capacity:
            # explicit out_capacity is the caller's pin: one attempt,
            # drops reported via out.dropped (pre-substrate semantics)
            return attempt_randjoin(out_capacity)[0]
        # The Cor-3 bound behind the default capacity is w.h.p. and only
        # holds for large-enough fragments; when we picked the buffer,
        # recover from overflow through the shared retry loop (the route
        # capacities grow with the same factor as the output buffer).
        (out, rep), factor, attempts = run_with_capacity(
            attempt_randjoin,
            CapacityPolicy.fixed(out_capacity, max_retries=3))
        _attach_capacity(rep, factor, attempts)
        return out, rep
    if algorithm == "broadcast":
        from repro.cluster.capacity import CapacityPolicy, run_with_capacity
        from repro.core.broadcastjoin import broadcast_join
        bc_sub = _resolve_substrate(substrate, t_machines)

        def attempt_broadcast(cap):
            out, rep = broadcast_join(s_keys, s_rows, t_keys, t_rows,
                                      t_machines=t_machines,
                                      out_capacity=int(cap),
                                      substrate=bc_sub,
                                      small_side=small_side)
            return (out, rep), int(np.asarray(out.dropped).max())

        if not defaulted_capacity:
            return attempt_broadcast(out_capacity)[0]
        # broadcast's per-machine output is not theorem-bounded (the big
        # side's deal decides it); the Theorem-6-style default plus the
        # shared retry loop recovers from the unlucky layouts.
        (out, rep), factor, attempts = run_with_capacity(
            attempt_broadcast,
            CapacityPolicy.fixed(out_capacity, max_retries=3))
        _attach_capacity(rep, factor, attempts)
        return out, rep
    from repro.core.repartition import repartition_join
    return repartition_join(s_keys, s_rows, t_keys, t_rows,
                            t_machines=t_machines, out_capacity=out_capacity,
                            substrate=_resolve_substrate(substrate,
                                                         t_machines),
                            donate=donate)


import functools as _functools


@_functools.lru_cache(maxsize=1)
def _dense_moe_jit():
    import jax
    from repro.models.moe import moe_layer
    return jax.jit(moe_layer, static_argnames=("cfg", "act"))


def moe_dispatch(params, x, cfg, *, mode: Optional[str] = None,
                 t_machines: int = 8,
                 substrate: Optional[Substrate] = None, policy=None,
                 act: str = "swiglu", rng=None):
    """One MoE layer with dispatch as a first-class cluster workload.

    Token->expert routing is the skew-join problem (tokens keyed by
    expert id; a hot expert is Join Product Skew), so it dispatches like
    :func:`join`.  Returns ``(y, report)`` — y shaped like x, and an
    AlphaKReport whose per-slot/per-expert workload vectors
    (``report.slot_workload`` / ``report.expert_workload``) are the
    measured dispatch balance.

    mode (default ``cfg.dispatch``):

    * ``"capacity"`` — the dense capacity-factor layer
      (:func:`repro.models.moe.moe_layer`); hot experts DROP tokens
      (``report.total_dropped``) — the Standard-Repartition-Join
      analogue.
    * ``"alpha_k"``  — the dense StatJoin-planned layer: hot-expert
      replicas + the Theorem-6 slot capacity from
      ``CapacityPolicy.moe_dispatch()``.
    * ``"cluster"``  — route tokens through the instrumented cluster
      exchange (:func:`repro.core.moe_dispatch.cluster_moe_dispatch`):
      per-expert counts taped by the collectives, ``plan_slots`` driven
      by the planner's CountMin/heavy-hitter estimate of the routing
      histogram, capacities from ``CapacityPolicy`` with
      retry-on-overflow.  Needs the token count to divide over
      ``t_machines``.
    * ``"auto"``     — sketch the routing ids once
      (:func:`repro.planner.plan_moe_query`), score the three modes in
      the cost model, dispatch to the winner; the report carries the
      :class:`QueryPlan` exactly like ``sort``/``join``.

    rng: RandJoin-style ``replica_choice="random"`` draw for the dense
    alpha_k layer (required there, unused elsewhere).
    """
    import dataclasses as _dc

    mode = cfg.dispatch if mode is None else mode
    if mode not in MOE_DISPATCH_MODES + (AUTO,):
        raise ValueError(f"unknown dispatch mode {mode!r}; expected one "
                         f"of {MOE_DISPATCH_MODES + (AUTO,)}")
    d = int(np.shape(x)[-1])
    tt = int(np.prod(np.shape(x)[:-1]))
    e, k = int(cfg.num_experts), int(cfg.top_k)

    plan = sketch_phases = None
    if mode in (AUTO, "cluster"):
        if tt % t_machines:
            raise ValueError(
                f"moe_dispatch mode {mode!r} shards tokens over machines: "
                f"token count {tt} must divide over t_machines={t_machines}")
        from repro.planner import expert_counts_estimate, plan_moe_query
        plan, sketch_phases = plan_moe_query(
            np.asarray(x).reshape(tt, d), params["router"],
            t_machines=t_machines, num_experts=e, top_k=k,
            extra_slots=cfg.extra_slots,
            capacity_factor=cfg.capacity_factor,
            substrate=_resolve_substrate(substrate, t_machines))
        if mode == AUTO:
            mode = plan.algorithm

    if mode == "cluster":
        from repro.core.moe_dispatch import cluster_moe_dispatch
        counts = expert_counts_estimate(plan.profile, e)
        y, report = cluster_moe_dispatch(
            params, x, cfg, t_machines=t_machines, counts=counts,
            substrate=substrate, policy=policy, act=act)
        _attach_plan(report, plan, sketch_phases)
        return y, report

    # dense modes: one jitted moe_layer call; the report's "machines"
    # are the dispatch slots (the layer is a single SPMD program — slot
    # balance IS its workload balance, and there are no exchange phases
    # to tape, hence alpha = 0).
    import jax.numpy as jnp
    from jax import lax

    from repro.core.alpha_k import AlphaKReport

    cfg_run = cfg if cfg.dispatch == mode else _dc.replace(cfg,
                                                           dispatch=mode)
    y, stats = _dense_moe_jit()(params, jnp.asarray(x), cfg=cfg_run,
                                act=act, rng=rng)
    slot_load = np.asarray(stats.slot_load, dtype=np.int64)
    n_slots = int(slot_load.shape[0])
    # exact host-side recount of the routing histogram (same f32
    # einsum/top_k expression the layer runs)
    xt = jnp.asarray(x).reshape(tt, d)
    ids = lax.top_k(jnp.einsum("td,de->te", xt.astype(jnp.float32),
                               jnp.asarray(params["router"])), k)[1]
    expert_workload = np.bincount(np.asarray(ids).reshape(-1),
                                  minlength=e)
    report = AlphaKReport(algorithm=f"moe[{mode}]", t=n_slots,
                          n_in=tt * k, n_out=tt * k, workload=slot_load,
                          phases=[])
    report.dispatch_mode = mode
    report.slot_workload = slot_load
    report.expert_workload = expert_workload
    report.k_slot = float(slot_load.max() / max(1.0, tt * k / n_slots))
    report.k_expert = float(expert_workload.max() / max(1.0, tt * k / e))
    report.total_dropped = int(np.asarray(stats.dropped))
    if plan is not None:
        _attach_plan(report, plan, sketch_phases)
    return y, report
